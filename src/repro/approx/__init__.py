"""Netlist approximation subsystem: pass-based circuit transforms with
interval worst-case error bounds, searched by the GA.

Built on the PR 3 circuit IR (`repro.circuit`): passes rebuild the netlist
(widths/levels re-derived by construction), the analyzer turns local
rewrite annotations + TRUNC semantics into per-logit worst-case error
bounds, and `circuit.cost.structural_cost` prices the approximated circuit
(TRUNC-aware width discounts) where the analytic `hw_model` cannot.

* `repro.approx.rewrite`  — rebuild walk, Pass / PassManager, DCE
* `repro.approx.passes`   — RoundCoeffsCSD / TruncateAccum / SimplifyActs
* `repro.approx.analyze`  — interval error propagation + logit bounds
                            (pure Python ints — jaxlint-enforced)
* `repro.approx.measure`  — simulation-measured counterparts of the bounds
* `repro.approx.budget`   — ApproxParams, greedy `fit_budget` under a
                            user-supplied logit-error budget

Quick use::

    net, compiled = circuit.compile_spec(cfg, spec, epochs=60)
    budget = approx.logit_budget(net, 0.01)          # 1% of logit range
    params, anet, rep = approx.fit_budget(net, budget)
    acc = circuit.netlist_accuracy(anet, compiled, xte, yte)
    print(rep.area_gain, rep.bound)                  # proven error ceiling

The GA searches the same knobs as genes: `LayerMin.csd_drop` / `.lsb` and
`ModelMin.argmax_lsb` (see `core.ga` / `core.batch_eval`).
"""
from repro.approx import analyze, budget, measure, passes, rewrite  # noqa: F401
from repro.approx.analyze import (decision_error_bound,  # noqa: F401
                                  logit_error_bound,
                                  propagate_errors)
from repro.approx.measure import measured_max_logit_error  # noqa: F401
from repro.approx.budget import (ApproxParams, BudgetReport,  # noqa: F401
                                 PricedApprox, approximate,
                                 approximate_and_price, build_passes,
                                 evaluate_netlist, fit_budget, logit_budget)
from repro.approx.passes import (RoundCoeffsCSD, SimplifyActs,  # noqa: F401
                                 TruncateAccum, product_info, truncate_csd)
from repro.approx.rewrite import Pass, PassManager, rebuild  # noqa: F401
