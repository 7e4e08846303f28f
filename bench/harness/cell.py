"""Cells, configurations and traffic mixes, found by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: it names a
configuration (``configs/<config>.json`` via its ``file``) and a traffic
mix (``traffic/<traffic>.json``).  Everything a cell needs is read from
those files; nothing about a particular cell lives in code.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

from harness.traffic import quantiles

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    # -- sizes ---------------------------------------------------------------

    @property
    def spec(self) -> Dict[str, Any]:
        return self.config["spec"]

    @property
    def serve(self) -> Dict[str, Any]:
        return self.config["serve"]

    @property
    def prompt_max(self) -> int:
        """The longest prompt the mix offers (its block's top quantile)."""
        return offered_max(self.traffic, "prompt")

    @property
    def output_max(self) -> int:
        """The longest output the mix offers."""
        return offered_max(self.traffic, "output")

    @property
    def pages_per_slot(self) -> int:
        """Pages one slot can hold at its worst: the longest prompt, the
        longest output plus the K tokens a last step may overshoot it by,
        and the page-growth horizon of one sync round,
        (sync_every + 1) * (K + 1) tokens ahead of the committed end."""
        K = int(self.spec["K"])
        horizon = (int(self.serve["sync_every"]) + 1) * (K + 1)
        tokens = self.prompt_max + self.output_max + K + horizon
        return -(-tokens // int(self.serve["page_size"]))

    def page_bytes(self) -> int:
        """KV bytes of one page across the target and the draft."""
        ps = int(self.serve["page_size"])
        total = 0
        for m in (self.config, self.config["draft"]):
            total += (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
                      * m["head_dim"] * ps * dtype_bytes(self.config))
        return total

    @property
    def batch(self) -> int:
        """Slots: the most whose worst-case pages fit the KV budget the
        configuration states (measured for its chip)."""
        return max(1, int(self.serve["kv_budget_bytes"])
                   // (self.pages_per_slot * self.page_bytes()))

    @property
    def num_pages(self) -> int:
        return self.batch * self.pages_per_slot + 1

    def reported(self, kind: str) -> List[Dict[str, Any]]:
        """The end-to-end (``kind="end_to_end"``) or per-layer metrics that
        this cell reports."""
        out = []
        for m in getattr(self, kind):
            cells = m.get("workloads")
            if cells is None or self.name in cells:
                out.append(m)
        return out


def offered_max(mix: Dict[str, Any], which: str) -> int:
    return int(quantiles(mix[which], int(mix["block"])).max())


def dtype_bytes(config: Dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[config["dtype"]]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration and
    traffic files read."""
    spec = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(os.path.join(ROOT, conf["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])
