"""Wall seconds per campaign in the program's XML export: the duration of
the ``model.export`` spans (``model_io.to_xml``, one per machine) in the
traced part, over the campaigns traced."""


def read(m):
    durs = [e["dur"] for e in m["program_spans"]
            if e.get("ph") == "X" and e.get("name") == "model.export"]
    n = m["counters"].get("campaigns")
    return sum(durs) / 1e9 / n if durs and n else None
