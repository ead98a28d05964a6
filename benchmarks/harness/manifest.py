"""`BENCHMARK.json` and the files it names. Everything that belongs to
one configuration, one traffic mix, one generator or one metric is a
file of its own, found here BY NAME: this module holds no table of
cells, generators, configurations or readers.

  config   <name>  -> the `file` its entry in BENCHMARK.json gives
  traffic  <name>  -> benchmarks/traffic/<name>.json   {"generator": g}
  generator <g>    -> benchmarks/generators/<g>.py     class Generator
  metric   <name>  -> benchmarks/metrics/<name>.json   {"reader": r, ...}
  reader   <r>     -> benchmarks/readers/<r>.py        def read(params, run)
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(Exception):
    """BENCHMARK.json, or a file it names, is not what the harness can
    run."""


def load(root: Path = ROOT) -> dict:
    try:
        return json.loads((root / "BENCHMARK.json").read_text())
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read BENCHMARK.json: {e}") from None


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from None


def _module(path: Path, attr: str):
    if not path.is_file():
        raise ManifestError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{path.parent.name}.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not hasattr(mod, attr):
        raise ManifestError(f"{path} defines no `{attr}`")
    return getattr(mod, attr)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json (has: "
        f"{[w['name'] for w in manifest['workloads']]})")


def config_of(manifest: dict, w: dict, root: Path = ROOT) -> dict:
    for c in manifest["configs"]:
        if c["name"] == w["config"]:
            return _json(root / c["file"])
    raise ManifestError(f"workload {w['name']!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")


def traffic_of(w: dict, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "traffic" / f"{w['traffic']}.json")


def generator_of(traffic: dict, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir / "generators" / f"{traffic['generator']}.py",
                   "Generator")


def metric_params(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "metrics" / f"{name}.json")


def reader_of(params: dict, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir / "readers" / f"{params['reader']}.py", "read")


def metrics_for(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of `section` ("end_to_end" / "per_layer") that this
    workload reports: those that list it, and those that list none."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def problems(manifest: dict, root: Path = ROOT) -> list[str]:
    """Everything about the manifest and its files that a run, or the
    contract, would trip over. Empty when sound."""
    bench_dir = root / "benchmarks"
    out: list[str] = []

    def name_ok(what: str, n) -> None:
        if not isinstance(n, str) or not NAME.match(n):
            out.append(f"{what}: {n!r} is not an allowed name")

    def line_ok(what: str, s) -> None:
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            out.append(f"{what}: not 1-200 characters on one line")

    want_keys = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
    if set(manifest) != want_keys:
        out.append(f"keys {sorted(manifest)} != {sorted(want_keys)}")
        return out
    if not 1 <= manifest["run_seconds"] <= 51:
        out.append("run_seconds outside 1..51")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if len(configs) != len(manifest["configs"]) \
            or len(cells) != len(manifest["workloads"]):
        out.append("two configs or two workloads share a name")
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        out.append("two metrics share a name")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        for r in c["reduced"]:
            name_ok(f"config {c['name']} reduced", r)
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            out.append(f"config {c['name']}: file outside paths")
        chips = [w.get("chips") for w in manifest["workloads"]
                 if w.get("config") == c["name"]]
        if not chips:
            out.append(f"config {c['name']}: no workload uses it")
        if not (root / c["file"]).is_file():
            out.append(f"config {c['name']}: {c['file']} does not exist")
        elif chips and all(n in (1, 4) for n in chips):
            # the deployment states the chips it is laid out on: the
            # most that any of its cells asks for
            try:
                got = _json(root / c["file"]).get("cluster", {}).get("chips")
            except ManifestError as e:
                out.append(f"config {c['name']}: {e}")
            else:
                if got != max(chips):
                    out.append(f"config {c['name']}: cluster.chips {got!r} "
                               f"is not its workloads' most, {max(chips)}")
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for key in ("name", "config", "traffic"):
            name_ok(f"workload {key}", w[key])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            generator_of(traffic_of(w, bench_dir), bench_dir)
        except (ManifestError, KeyError) as e:
            out.append(f"workload {w['name']}: {e}")
    # the contract's rule: of n cells at most half, rounded down, take
    # four chips, and one always may
    four = sum(w.get("chips") == 4 for w in manifest["workloads"])
    cap = max(1, len(manifest["workloads"]) // 2)
    if four > cap:
        out.append(f"{four} workloads take four chips; of "
                   f"{len(manifest['workloads'])} at most {cap} may")
    if "setup_s" not in e2e:
        out.append("no end-to-end metric setup_s")
    for section, keys in (
            ("end_to_end", {"name", "unit", "better", "bound", "source"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"})):
        for m in manifest[section]:
            if set(m) - {"workloads"} != keys:
                out.append(f"{section} {m.get('name')}: keys {sorted(m)}")
                continue
            name_ok(section, m["name"])
            if not UNIT.match(m["unit"]):
                out.append(f"{m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"{m['name']}: source {m['source']!r}")
            if section == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"{m['name']}: end-to-end source")
                if not 0.01 <= m["bound"] <= 0.25:
                    out.append(f"{m['name']}: bound {m['bound']}")
            else:
                line_ok(f"{m['name']} layer", m["layer"])
                if m["moves"] not in e2e:
                    out.append(f"{m['name']}: moves unknown metric")
                if "workloads" not in m:
                    out.append(f"{m['name']}: no workloads list")
            for wname in m.get("workloads", []):
                if wname not in cells:
                    out.append(f"{m['name']}: unknown workload {wname}")
                elif section == "per_layer" and m["moves"] in e2e and not any(
                        x["name"] == m["moves"] for x in
                        metrics_for(manifest, "end_to_end", wname)):
                    out.append(f"{m['name']}: {wname} does not report "
                               f"{m['moves']}")
            try:
                reader_of(metric_params(m["name"], bench_dir), bench_dir)
            except (ManifestError, KeyError) as e:
                out.append(f"{m['name']}: {e}")
    for wname in cells:
        got = [m["name"] for m in metrics_for(manifest, "end_to_end", wname)]
        if "setup_s" not in got or len(got) < 2:
            out.append(f"workload {wname}: end-to-end metrics {got}")
        if not metrics_for(manifest, "per_layer", wname):
            out.append(f"workload {wname}: no per-layer metric")
    return out
