"""Run one cell of ``BENCHMARK.json`` once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout.  Order: enable the program's persistent
compile cache, build the cell from the seed (weights on the device), warm
up the cell's own shapes, drive the window for ``--seconds``, then check
what the window produced against the references.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced, a
``breakdown``; the numbers compared come last under ``compared``, each
with its limit, and again as the last lines of standard error.

Exits non-zero with no result line when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import os
import time

T_START = time.perf_counter()
# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402
from types import SimpleNamespace                            # noqa: E402

import numpy as np                                           # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
#: seconds of the window a traced run records
TRACE_S = 10.0


class NoChip(RuntimeError):
    pass


# ---- discovery by name ------------------------------------------------------
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, name: str, root: Path = ROOT):
    """(cell, configuration, mix) of the cell ``name``: the configuration
    file that ``BENCHMARK.json`` names and ``bench/mixes/<traffic>.json``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" /
                      f"{cell['traffic']}.json").read_text())
    return cell, config, mix


def metrics_of(spec: dict, cell: dict, layer: bool) -> list:
    """The cell's metric entries: per-layer ones list the cell (or, with no
    ``workloads`` key, every cell that reports the metric they move)."""
    e2e = [m for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in names]


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the run ------------------------------------------------------------------
def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def window(drv, seconds: float, trace: bool):
    """Drive flushes until ``seconds`` have passed; returns the records,
    the window's wall seconds and its counters.  Traced, the profiler
    records the first ``TRACE_S`` seconds, after one flush of its own."""
    import jax
    lowered = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: lowered.__setitem__(0, lowered[0] + 1)
        if ev == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
    from bench.sut import annotate
    drv.start(trace)
    if trace:
        drv.flush()                  # the profiler's own first-call cost
    flushes = []
    span = annotate(trace, "bench.window")
    span.__enter__()
    base = lowered[0]
    t0 = time.perf_counter()
    while True:
        f = drv.flush()
        f.traced = drv.trace
        flushes.append(f)
        elapsed = time.perf_counter() - t0
        if drv.trace and (elapsed >= TRACE_S or elapsed >= seconds):
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            drv.trace = False
        if elapsed >= seconds:
            break
    wall = time.perf_counter() - t0
    return (flushes, wall, sum(f.misses for f in flushes) + lowered[0] - base,
            sum(f.og_plans for f in flushes),
            sum(f.og_dispatches for f in flushes))


def execute(spec: dict, name: str, seed: int, seconds: float, trace: bool,
            *, require_tpu: bool = True, config: dict | None = None,
            mix: dict | None = None, root: Path = ROOT) -> dict:
    """Build, warm up and drive one cell; returns what the result and the
    check read.  The drive is closed and freed before this returns.
    ``config`` and ``mix`` stand in for the cell's files (tests run the
    harness on the CPU at small sizes)."""
    cell, cfg_file, mix_file = cell_parts(spec, name, root)
    config, mix = config or cfg_file, mix or mix_file
    devs = devices(cell["chips"], require_tpu)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import sut
    from bench.peaks import peaks
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peak = peaks(devs[0].device_kind) if require_tpu else None

    drv = sut.drive(config, mix, seed, root)
    drv.warm()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    flushes, wall, compiles, og_plans, og_disp = window(drv, seconds, trace)
    summary = None
    if trace:
        from bench import trace as tr
        summary = tr.read(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    mem = [d.memory_stats() or {} for d in devs]
    ans = drv.answers()
    params = getattr(drv, "params", None)
    served = drv.served
    drv.close()
    del drv
    gc.collect()
    return dict(cell=cell, config=config, mix=mix, root=root, devs=devs,
                peak=peak, setup_s=setup_s, flushes=flushes, wall=wall,
                compiles=compiles, og_plans=og_plans, og_dispatches=og_disp,
                summary=summary, served=served, answers=ans, params=params,
                mem_peak=max(int(m.get("peak_bytes_in_use", 0)) for m in mem))


def compare(x: dict, seed: int, control=None) -> dict:
    """The numbers compared, each with its limit.  ``control`` (a numpy
    dtype) puts the reference planner in that precision, and the
    reference forward one step below the configuration's matmul precision
    (three-pass bfloat16 for ``float32``), in the program's place.  The
    reference forward is the module the configuration's ``model`` names."""
    from bench import check
    from bench.reference import model_module
    config, mix, ans, root = x["config"], x["mix"], x["answers"], x["root"]
    if mix["mode"] == "online":
        nums = check.online(config, mix, seed, ans, control=control,
                            root=root)
    else:
        nums = check.waves(config, mix, seed, ans, control=control,
                           root=root)
    if x["served"]:
        model = config["model"]
        below = model_module(model, root).CONTROL_BELOW
        nums.update(check.logits(
            model, x["params"], ans["kept"], ans["tokens"],
            "highest" if control is None
            else below[model["matmul_precision"]], root=root))
    limits = config["limits"]
    return {k: {"value": v, "limit": limits.get(k)} for k, v in nums.items()}


def result(spec: dict, x: dict, seed: int, trace: bool,
           root: Path = ROOT) -> dict:
    """The result object of one run: metrics, device, check."""
    t0 = time.perf_counter()
    compared = {k: c for k, c in compare(x, seed).items()
                if c["limit"] is not None}
    x["check_s"] = time.perf_counter() - t0
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    flushes, wall, devs = x["flushes"], x["wall"], x["devs"]
    n_req = sum(f.n for f in flushes)
    ms = [f.ms for f in flushes]
    e2e = {"flush_ms.p50": percentile(ms, 50),
           "flush_ms.p95": percentile(ms, 95),
           "req_per_s": n_req / wall, "setup_s": x["setup_s"]}
    # what the per-layer readers read
    run = SimpleNamespace(flushes=flushes, window_s=wall,
                          compiles=x["compiles"], og_plans=x["og_plans"],
                          og_dispatches=x["og_dispatches"],
                          trace=x["summary"], model=x["config"].get("model"),
                          seq=x["mix"].get("prompt_tokens", 0),
                          peak=x["peak"], root=x["root"])
    metrics = {}
    for m in metrics_of(spec, x["cell"], trace):
        v = e2e[m["name"]] if not trace else reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": x["mem_peak"]}
    out = {"correct": bool(correct), "attempted": n_req,
           "failed": int(sum(f.late for f in flushes)), "metrics": metrics,
           "device": dev}
    if x["summary"] is not None:
        dev["busy_s"] = x["summary"].busy_s
        dev["window_s"] = x["summary"].window_s
        out["breakdown"] = x["summary"].breakdown()
    out["compared"] = compared
    return out


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run of one cell; returns the result object (with the flush
    times and the check's seconds under ``_flush_ms`` and ``_check_s``)."""
    x = execute(spec, name, seed, seconds, trace, **kw)
    out = result(spec, x, seed, trace, kw.get("root", ROOT))
    out["_flush_ms"] = [f.ms for f in x["flushes"]]
    out["_check_s"] = x["check_s"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        out = run_cell(load_spec(), args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    print(f"bench: {len(out['_flush_ms'])} flushes, first "
          f"{out['_flush_ms'][0]:.1f} ms, max {max(out['_flush_ms']):.1f} ms; "
          f"check {out['_check_s']:.1f} s; process "
          f"{time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    del out["_flush_ms"], out["_check_s"]
    for k, c in out["compared"].items():
        print(f"compared {k} = {float(c['value'])!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
