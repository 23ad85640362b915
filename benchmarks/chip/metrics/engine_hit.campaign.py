"""Share of experiment requests the measurement engines answered from
their cache or by in-wave deduplication, over the window's campaigns
(``EngineStats`` summed over each campaign's machines)."""


def read(m):
    rate = m["counters"].get("engine_hit_rate")
    return None if rate is None else 100.0 * rate
