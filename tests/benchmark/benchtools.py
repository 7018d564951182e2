"""Shared pieces of the benchmark's CPU tests: the toy cells that exist
only under ``tests/benchmark/toy`` and a helper that runs the harness
in-process on them (a function argument lifts the TPU requirement; the
command line has no switch for it)."""

import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")
for p in (ROOT, TOY):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_toy(cell, trace, seconds=0.6, seed=3, out_dir=None,
            manifest_path=None, extra_roots=()):
    """``benchmark.run.main`` on a toy cell; returns (exit code, the
    last line parsed, all earlier lines)."""
    from benchmark import run
    if out_dir is not None:
        os.environ["BENCHMARK_OUT_DIR"] = str(out_dir)
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            rc = run.main(
                ["--workload", cell, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", str(trace)],
                manifest_path=str(manifest_path or os.path.join(
                    TOY, "BENCHMARK.toy.json")),
                extra_roots=[TOY, *map(str, extra_roots)],
                require_tpu=False)
    finally:
        os.environ.pop("BENCHMARK_OUT_DIR", None)
    lines = buf.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines[:-1]


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


# ------------------------------------------------------------ the contract
FIT_CELLS = ["resnet50.fit_cached", "vgg16.fit_cached"]

#: ``per_layer`` of ``BENCHMARK.json`` BEGINS with these, in this order,
#: and each is reported at least in these cells; whatever a later PR
#: appends after them is free.  A ``benchmark`` PR that retires a metric
#: edits this list; no other PR does.
ACCEPTED_PER_LAYER = [(name, FIT_CELLS) for name in (
    "data_stage_share", "dispatches_per_step", "compiles_in_window",
    "device_mfu", "cache_misses_warm", "device_idle_share",
    "unit_stall_share", "hbm_peak_gib",
    "fit_dispatch_ms", "setup_trace_lower_s", "setup_backend_s")]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
#: what ``reduced`` may never name: a width
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj)\w*size|_dim$|_rank$"
    r"|head_size|^expand$|expansion|experts_per_tok")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(AssertionError):
    """An entry of a manifest, or a file it names, breaks the contract;
    the message starts with the entry at fault."""


def _need(ok, where, what):
    if not ok:
        raise ManifestError(f"{where}: {what}")


def _line(text, where, what):
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text, where,
          f"{what} has to be 1 to 200 characters on one line")


def _keys(entry, required, optional, where):
    _need(required <= set(entry) <= required | optional, where,
          f"keys {sorted(entry)} are not {sorted(required)}"
          + (f" plus any of {sorted(optional)}" if optional else ""))


def check_config_file(listed, cfg):
    """The configuration's file against its entry: same ``source`` and
    ``reduced``, and every cut explained: for each name in ``reduced``
    the source's own value under ``published``, the value it runs with
    under the key itself, and one ``deployment`` string (over how many
    chips each layer is divided and how; which layers are left out)."""
    where = f"configs/{listed['name']}"
    _need(cfg.get("source") == listed["source"], where,
          "the file's source differs from the entry's")
    _need(cfg.get("reduced") == listed["reduced"], where,
          f"the file's reduced {cfg.get('reduced')} differs from the "
          f"entry's {listed['reduced']}")
    published = cfg.get("published", {})
    _need(isinstance(published, dict), where, "published is no object")
    for key in listed["reduced"]:
        _need(key in published and key in cfg, where,
              f"reduced names {key!r}, which the file does not explain: "
              f"it needs published[{key!r}] (the source's value) and "
              f"{key!r} itself (the value it runs with)")
        _need(published[key] != cfg[key], where,
              f"reduced names {key!r}, which runs at its published value")
    _need(set(published) <= set(listed["reduced"]), where,
          f"published explains {sorted(set(published) - set(listed['reduced']))}"
          f", which reduced does not list")
    if listed["reduced"]:
        _need(isinstance(cfg.get("deployment"), str)
              and cfg["deployment"].strip(), where,
              "a cut configuration needs a deployment string")


def check_manifest(manifest, lookup, accepted=(), allowed_chips=(1, 4)):
    """What the benchmark's tests hold of a manifest and of the files it
    names, found through ``lookup`` (a ``benchmark.run.Lookup``): the
    contract's limits, every cell resolved to its files, every metric to
    a reader that declares the same, every cut configuration explained.
    ``accepted`` is ``[(name, cells)]``: the names ``per_layer`` has to
    begin with, in order, each reported at least in those cells.  Raises
    ``ManifestError`` naming the entry at fault."""
    m = manifest
    _need(set(m) == TOP_KEYS, "manifest", f"keys {sorted(m)}")
    _need(isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32,
          "command", "1 to 32 strings")
    for word in m["command"]:
        _line(word, "command", f"word {word!r}")
        _need(not word.startswith("/") and ".." not in word.split("/"),
              "command", f"{word!r} leads out of the checkout")
    _need(isinstance(m["paths"], list) and 1 <= len(m["paths"]) <= 16
          and all(PATH.match(p) for p in m["paths"]), "paths",
          f"{m['paths']} are not 1 to 16 relative directories")
    seconds = m["run_seconds"]
    _need(isinstance(seconds, int) and 1 <= seconds <= 51, "run_seconds",
          f"{seconds} is no whole number from 1 to 51")
    # a full check with all 24 cells has to fit into 43,200 s
    _need((2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200,
          "run_seconds", f"a full check of 24 cells at {seconds} s does "
          f"not fit into 43,200 s")

    cells = m["workloads"]
    names = [c["name"] for c in cells]
    _need(1 <= len(cells) <= 24, "workloads", "1 to 24 cells")
    _need(1 <= len(m["configs"]) <= 24, "configs", "1 to 24 configurations")
    _need(len(set(names)) == len(names), "workloads", "a name twice")
    _need(len({(c["config"], c["traffic"]) for c in cells}) == len(cells),
          "workloads", "a pair of configuration and traffic twice")
    _need(sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4),
          "workloads", "more than a quarter of the cells ask for 4 chips")
    configs = {c["name"]: c for c in m["configs"]}
    _need(len(configs) == len(m["configs"]), "configs", "a name twice")
    _need(len({c["file"] for c in m["configs"]}) == len(configs),
          "configs", "two configurations share a file")

    for c in m["configs"]:
        where = f"configs/{c.get('name')}"
        _keys(c, CONFIG_KEYS, set(), where)
        _need(NAME.match(c["name"]), where, "the name")
        _line(c["source"], where, "source")
        _line(c["why"], where, "why")
        _need(any(w["config"] == c["name"] for w in cells), where,
              "no cell uses it")
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
              and all(isinstance(k, str) and NAME.match(k)
                      for k in c["reduced"]), where,
              f"reduced {c['reduced']} is not at most 16 key names")
        for key in c["reduced"]:
            _need(not WIDTH.search(key), where,
                  f"reduced names the width {key!r}; no width is ever cut")
        directory, base = os.path.split(c["file"])
        _need(base == c["name"] + ".json" and directory in
              [p + "/configs" for p in m["paths"]], where,
              f"file {c['file']!r} is not <one of paths>/configs/"
              f"{c['name']}.json")
        found = lookup.path("configs", c["name"], (".json",))
        in_repo = os.path.join(ROOT, c["file"])
        _need(not os.path.isfile(in_repo)
              or os.path.samefile(found, in_repo), where,
              f"the lookup finds {found}, the entry names {c['file']}")
        check_config_file(c, lookup.data("configs", c["name"]))

    for c in cells:
        where = f"workloads/{c.get('name')}"
        _keys(c, CELL_KEYS, set(), where)
        for key in ("name", "config", "traffic"):
            _need(NAME.match(c[key]), where, f"{key} {c[key]!r}")
        _need(c["config"] in configs, where,
              f"no configuration {c['config']!r}")
        _need(c["chips"] in allowed_chips, where, f"chips {c['chips']}")
        _line(c["why"], where, "why")
        data = lookup.data("workloads", c["name"])
        for key in ("config", "traffic", "chips", "why"):
            _need(data.get(key) == c[key], where,
                  f"the cell's file says {key} {data.get(key)!r}, the "
                  f"entry {c[key]!r}")
        lookup.path("traffic", c["traffic"])
        driver = lookup.module("drivers", data["driver"])
        _need(callable(getattr(driver, "setup", None))
              and callable(getattr(driver, "measure", None)), where,
              f"driver {data['driver']!r} lacks setup or measure")

    e2e = {e["name"]: e for e in m["end_to_end"]}
    _need(1 <= len(m["end_to_end"]) <= 16, "end_to_end", "1 to 16 metrics")
    _need(1 <= len(m["per_layer"]) <= 128, "per_layer", "1 to 128 metrics")
    _need("setup_s" in e2e, "end_to_end", "no setup_s")
    _need(set(e2e["setup_s"].get("workloads", names)) == set(names),
          "end_to_end/setup_s", "every cell reports it")
    every = m["end_to_end"] + m["per_layer"]
    _need(len({e["name"] for e in every}) == len(every), "metrics",
          "a name twice")
    for group, kind, keys in (("end_to_end", "end_to_end", E2E_KEYS),
                              ("per_layer", "layer_metrics", LAYER_KEYS)):
        for e in m[group]:
            where = f"{group}/{e.get('name')}"
            _keys(e, keys, {"workloads"}, where)
            _need(NAME.match(e["name"]), where, "the name")
            _need(UNIT.match(e["unit"]), where, f"unit {e['unit']!r}")
            _need(e["better"] in ("lower", "higher"), where, "better")
            _need(set(e.get("workloads", [])) <= set(names), where,
                  f"lists a cell the manifest lacks: {e.get('workloads')}")
            reader = lookup.module(kind, e["name"])
            _need(callable(getattr(reader, "read", None)), where,
                  "its reader has no read(record)")
            declared = {"unit": reader.UNIT, "better": reader.BETTER,
                        "source": reader.SOURCE}
            if group == "per_layer":
                declared["layer"] = reader.LAYER
                # ``moves`` is the manifest's alone: one reader serves
                # cells whose end-to-end metrics differ
                _need(not hasattr(reader, "MOVES"), where,
                      "its reader declares MOVES")
            for key, value in declared.items():
                _need(e[key] == value, where,
                      f"{key} is {e[key]!r}, its reader declares {value!r}")
    for e in m["end_to_end"]:
        where = f"end_to_end/{e['name']}"
        _need(0.01 <= e["bound"] <= 0.1, where, f"bound {e['bound']}")
        _need(e["source"] in ("host_clock", "device_trace"), where,
              f"source {e['source']!r}")
    for e in m["per_layer"]:
        where = f"per_layer/{e['name']}"
        _need(e["source"] in SOURCES, where, f"source {e['source']!r}")
        _line(e["layer"], where, "layer")
        _need(e["moves"] in e2e, where, f"moves {e['moves']!r}, which is "
              f"no end-to-end metric")
        # reported only where the metric it moves is reported
        moved = e2e[e["moves"]].get("workloads", names)
        _need(set(e.get("workloads", names)) <= set(moved), where,
              f"is reported in a cell that does not report {e['moves']}")
    for c in cells:
        where = f"workloads/{c['name']}"
        mine = [e for e in m["end_to_end"]
                if c["name"] in e.get("workloads", names)]
        _need(len(mine) >= 2, where,
              "reports no end-to-end metric besides setup_s")
        _need(any(c["name"] in e.get("workloads", names)
                  for e in m["per_layer"]), where,
              "reports no per-layer metric")

    listed = [e["name"] for e in m["per_layer"]]
    for i, (name, at_least) in enumerate(accepted):
        _need(listed[i:i + 1] == [name], f"per_layer/{listed[i]}"
              if i < len(listed) else "per_layer",
              f"entry {i} has to be the accepted {name!r}: new entries go "
              f"after the accepted ones, which keep their order")
        entry = m["per_layer"][i]
        _need(set(at_least) <= set(entry.get("workloads", names)),
              f"per_layer/{name}", f"no longer lists {at_least}")
    _need(len(json.dumps(m)) < 64 * 1024, "manifest", "over 64 KiB")
