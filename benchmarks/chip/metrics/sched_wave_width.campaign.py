"""Mean fused-wave width of the window's campaigns (experiments per
submitted wave, ``CampaignResult.mean_wave_width``)."""


def read(m):
    return m["counters"].get("mean_wave_width")
