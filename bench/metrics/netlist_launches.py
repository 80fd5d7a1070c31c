"""Netlist simulation: launches of the netlist engine per generation,
counted from the window's ``netlist_sim.padding`` events, one per launch
(the packed population launch, and one P = 1 launch per approximated
candidate)."""


def read(a):
    n = sum(1 for r in a.spans if r.get("kind") == "event"
            and r["name"] == "netlist_sim.padding")
    if not n or not a.window.generations:
        return None
    return n / a.window.generations
