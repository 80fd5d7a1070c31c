"""Netlist simulation padding: wave lanes carrying an operation over the
lanes launched, in percent, summed over the window's
``netlist_sim.padding`` events (``lanes_used`` / ``lanes_total``)."""


def read(a):
    pads = [r["attrs"] for r in a.spans if r.get("kind") == "event"
            and r["name"] == "netlist_sim.padding"]
    total = sum(p["lanes_total"] for p in pads)
    if not total:
        return None
    return 100.0 * sum(p["lanes_used"] for p in pads) / total
