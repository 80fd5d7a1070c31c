"""Population-batched netlist simulation: one `levels` launch scores P
packed candidates (ops.py), with its NumPy oracle (ref.py)."""
from repro.kernels.netlist_sim.ops import (population_accuracy,  # noqa: F401
                                           simulate_population)
from repro.kernels.netlist_sim.pack import (NOP,  # noqa: F401
                                            PackedNetlist, PackedPopulation,
                                            pack_netlist, pack_population,
                                            unpack_netlist)
from repro.kernels.netlist_sim.ref import \
    simulate_population_ref  # noqa: F401
