"""Finds everything a cell needs by the names in BENCHMARK.json.

A cell, a configuration, a traffic mix and a per-layer metric are files of
their own; nothing here knows any of them by name. A later PR adds
``workloads/<cell>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.json`` (and, for a new quantity, a reader
``layer_metrics/readers/<reader>.py``) under any directory listed in
``paths``, plus one entry in BENCHMARK.json, and edits no file.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# repo root = three levels above this file (benchmarks/chip/benchlib/spec.py)
DEFAULT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


class SpecError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """BENCHMARK.json plus the data files it names."""

    def __init__(self, root: str | None = None):
        self.root = os.path.abspath(root or DEFAULT_ROOT)
        self.doc = _load(os.path.join(self.root, "BENCHMARK.json"))

    # -- lookup ---------------------------------------------------------
    def find(self, kind: str, name: str, ext: str = ".json") -> str:
        """``<path>/<kind>/<name><ext>`` in the first of ``paths`` that has it."""
        if not NAME_RE.match(name):
            raise SpecError(f"bad name {name!r}")
        for p in self.doc["paths"]:
            f = os.path.join(self.root, p, kind, name + ext)
            if os.path.isfile(f):
                return f
        raise SpecError(f"no {kind}/{name}{ext} under paths {self.doc['paths']}")

    def workload_entry(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"workload {name!r} is not in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise SpecError(f"config {name!r} is not in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        """Everything one cell runs with, resolved."""
        w = self.workload_entry(name)
        c = self.config_entry(w["config"])
        return {
            "name": name,
            "chips": int(w["chips"]),
            "why": w["why"],
            "config_name": w["config"],
            "traffic_name": w["traffic"],
            "params": _load(self.find("workloads", name)),
            "model": _load(os.path.join(self.root, c["file"])),
            "traffic": _load(self.find("traffic", w["traffic"])),
            "end_to_end": self.metrics_of(name, "end_to_end"),
            "per_layer": self.metrics_of(name, "per_layer"),
        }

    def metrics_of(self, cell: str, group: str) -> list[dict]:
        """The metrics of ``group`` this cell reports: those that list it
        under ``workloads``, and those without the key."""
        return [
            m
            for m in self.doc[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def cell_kind(self, kind: str):
        """The runner module ``benchlib/cells/<kind>.py`` of a cell kind."""
        return self._module(os.path.join("benchlib", "cells"), kind)

    def traffic(self, name: str) -> dict:
        return _load(self.find("traffic", name))

    def layer_metric(self, name: str) -> dict:
        return _load(self.find("layer_metrics", name))

    def reader(self, reader_name: str):
        """The reader module ``layer_metrics/readers/<reader_name>.py``."""
        return self._module(os.path.join("layer_metrics", "readers"), reader_name)

    def _module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        tag = re.sub(r"[^A-Za-z0-9_]", "_", f"benchfile_{kind}_{name}")
        spec = importlib.util.spec_from_file_location(tag, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- self-check (what the contract refuses before any run) ----------
    def problems(self) -> list[str]:
        out: list[str] = []
        d = self.doc
        e2e = {m["name"]: m for m in d["end_to_end"]}
        cells = {w["name"] for w in d["workloads"]}
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in d[group]]
            if len(set(names)) != len(names):
                out.append(f"duplicate name in {group}")
            out += [f"bad name {n!r}" for n in names if not NAME_RE.match(n)]
        if "setup_s" not in e2e:
            out.append("no setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"bad unit {m['unit']!r} on {m['name']}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"bad better on {m['name']}")
            if m["source"] not in SOURCES:
                out.append(f"bad source on {m['name']}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"{m['name']} lists unknown cell {w}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"end-to-end {m['name']} has source {m['source']}")
            if not 0 < m["bound"] <= 0.1:
                out.append(f"bound of {m['name']} outside (0, 0.1]")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']} moves unknown {m['moves']}")
                continue
            moved = e2e[m["moves"]]
            for w in m.get("workloads", cells):
                if "workloads" in moved and w not in moved["workloads"]:
                    out.append(f"{m['name']} in {w}: {m['moves']} not reported there")
        for c in d["configs"]:
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append(f"config file {c['file']} missing")
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                out.append(f"config {c['name']} used by no cell")
        for w in d["workloads"]:
            if w["chips"] not in (1, 4):
                out.append(f"{w['name']}: chips {w['chips']}")
            if len(w["why"]) > 200:
                out.append(f"{w['name']}: why over 200 characters")
            for kind, n in (("workloads", w["name"]), ("traffic", w["traffic"])):
                try:
                    self.find(kind, n)
                except SpecError as e:
                    out.append(str(e))
            if len(self.metrics_of(w["name"], "end_to_end")) < 2:
                out.append(f"{w['name']} reports no end-to-end metric besides setup_s")
            if not self.metrics_of(w["name"], "per_layer"):
                out.append(f"{w['name']} reports no per-layer metric")
        for m in d["per_layer"]:
            try:
                lm = self.layer_metric(m["name"])
                self.find(os.path.join("layer_metrics", "readers"), lm["reader"], ".py")
            except (SpecError, KeyError) as e:
                out.append(f"layer metric {m['name']}: {e}")
        return out
