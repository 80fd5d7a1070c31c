"""Find a cell's parts by name: its entry in BENCHMARK.json, its
configuration file, its traffic mix and its correctness limits."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# Every key of a configuration or traffic file is read by the harness or
# the reference, or is a note for the reader: a key outside these is
# refused rather than silently left unread.
CONFIG_KEYS = {"name", "source", "layer_dims", "baseline_bits",
               "input_bits", "netlist_warmup", "precision", "assumed"}
MIX_KEYS = {"name", "about", "seeds", "population", "generations", "epochs",
            "objective", "include_delay", "genes", "seed_specs", "warmup",
            "check", "netlist_warmup"}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # bench/configs/<config>.json
    mix: Dict               # bench/traffic/<traffic>.json
    limits: Dict            # bench/limits/<workload>.json
    end_to_end: List[Dict]  # the benchmark's metrics this cell reports
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def _known(what: str, d: Dict, keys) -> Dict:
    unknown = sorted(set(d) - keys)
    if unknown:
        raise KeyError(f"{what}: keys {unknown} are read by nothing; "
                       f"known: {sorted(keys)}")
    return d


def load(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _known(w["config"], json.loads(
        (root / configs[w["config"]]["file"]).read_text()), CONFIG_KEYS)
    data = config["assumed"]["dataset"]
    dims = config["layer_dims"]
    if (dims[0], dims[-1]) != (data["n_features"], data["n_classes"]):
        raise KeyError(f"{w['config']}: layer_dims {dims} do not fit the "
                       f"dataset's {data['n_features']} features and "
                       f"{data['n_classes']} classes")
    mix = _known(w["traffic"], json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text()), MIX_KEYS)
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return Cell(name, int(w["chips"]), config, mix, limits,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])
