"""Drive the system's main path once on one TPU and check what comes out.

    python chip_smoke.py

Run from the checkout root.  One process runs every phase in turn and any
failure propagates; each phase prints what it checked.  The phases:

* device   — platform, device kind, device count, JAX version;
* planner  — ``optimal_grouping`` through a ``PlannerService`` on the
  paper's MobileNetV2 setup, identical (β = 2.13) and different
  (β ~ U(0, 10)) deadlines: the dispatch fold at M = 96, and at M = 32 the
  dispatch fold, the fused device scan and the sequential reference DP,
  whose energies must agree bit for bit;
* online   — ``OnlineScheduler.run_batched`` over a Poisson stream at
  M = 1000, with zero deadline violations;
* sweep    — the compiled Pallas sweep kernel against its oracle grid;
* served   — ``repro.launch.serve.main`` with minitron-4b at its published
  widths and depth, offline and online, each checking the co-inference
  logits against the monolithic forward.

The last line of standard output is one JSON object naming the device.
Off a TPU the script exits non-zero before any phase and prints no such
line.
"""
from __future__ import annotations

import gc
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the served configuration: at least one flush offloads a batch of >= 2
#: users at these deadlines (β ∈ U(10, 30)) and this sequence length
SERVE_ARGV = ["--arch", "minitron-4b", "--full-width", "--users", "6",
              "--seq", "32", "--beta", "10", "30"]


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def phase_device():
    import jax
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}")
    return dev


def _same_plan(a, b) -> bool:
    return (a.energy == b.energy
            and [list(g) for g in a.groups] == [list(g) for g in b.groups]
            and (a.per_user_energy == b.per_user_energy).all())


def phase_planner(big: int = 96, small: int = 32) -> None:
    """Grouping energies: fused scan == dispatch fold == reference DP,
    bit for bit, at M = ``small``; the dispatch fold runs at ``big``."""
    from repro.core import (PlannerService, make_edge_profile, make_fleet,
                            mobilenet_v2_profile, optimal_grouping,
                            optimal_grouping_reference)
    profile = mobilenet_v2_profile()
    edge = make_edge_profile(profile)
    for name, beta in (("identical", 2.13), ("different", (0.0, 10.0))):
        for M in (big, small):
            fleet = make_fleet(M, profile, edge, beta=beta, seed=0)
            svc = PlannerService(profile, edge)
            disp = optimal_grouping(profile, fleet, edge, service=svc,
                                    dp_backend="dispatch")
            _check(len(disp.groups) > 0 and sum(map(len, disp.groups)) == M,
                   f"{name} M={M}: every user grouped")
            line = (f"[planner] {name} M={M}: dispatch E={disp.energy!r} "
                    f"groups={len(disp.groups)}")
            if M == small:
                fused = optimal_grouping(profile, fleet, edge, service=svc,
                                         dp_backend="fused")
                fs = svc.stats().fused_scan_latency()
                _check(fs["count"] >= 1 and fs["fallbacks"] == 0
                       and fs["routed"] == 0,
                       f"{name} M={M}: the fused scan ran on device ({fs})")
                ref = optimal_grouping_reference(profile, fleet, edge)
                _check(_same_plan(fused, disp),
                       f"{name} M={M}: fused {fused.energy!r} == dispatch "
                       f"{disp.energy!r} bit for bit")
                _check(_same_plan(disp, ref),
                       f"{name} M={M}: dispatch {disp.energy!r} == "
                       f"reference {ref.energy!r} bit for bit")
                line += (f" fused E={fused.energy!r} reference "
                         f"E={ref.energy!r} bit-equal=True")
            st = svc.stats()
            print(line + f" (compiles={st.misses}, "
                         f"dispatches={st.dispatches})")
            svc.close()


def phase_online(M: int = 1000, load_hz: float = 2.0, seed: int = 7) -> None:
    """A sustained Poisson stream drained through the batched event loop
    (the fleet-scale online row of ``benchmarks/scale_bench.py``)."""
    from repro.core import (OnlineScheduler, PlannerService,
                            make_edge_profile, make_fleet,
                            mobilenet_v2_profile, poisson_arrivals)
    profile = mobilenet_v2_profile()
    edge = make_edge_profile(profile)
    fleet = make_fleet(M, profile, edge, beta=(10.0, 30.0), seed=seed)
    arrivals = poisson_arrivals(M, load_hz * M, fleet, seed=seed)
    svc = PlannerService(profile, edge)
    sched = OnlineScheduler(profile, fleet, edge, policy="slack",
                            keep_frac=0.7, service=svc)
    sched.submit_many(sorted(arrivals, key=lambda a: a.arrival))
    res = sched.run_batched()
    st = svc.stats()
    print(f"[online] M={M}: flushes={res.n_flushes} "
          f"max_batch={max(res.batch_sizes)} violations={res.violations} "
          f"energy={res.energy!r} planner_compiles={st.misses}")
    svc.close()
    _check(res.violations == 0, f"online M={M}: zero violations")
    _check(res.n_flushes < M, f"online M={M}: flushes batch arrivals")


def phase_sweep(M: int = 96, interpret: bool = False) -> None:
    """The Pallas sweep kernel against ``jdob_sweep_ref``: same finite
    cells, rtol 1e-4, same argmin; compiled, its HLO holds the Mosaic
    custom call."""
    import functools

    import jax
    import numpy as np

    from repro.core import make_edge_profile, make_fleet, mobilenet_v2_profile
    from repro.kernels import jdob_sweep_op
    from repro.kernels.jdob_sweep import jdob_sweep_kernel
    from repro.kernels.ops import jdob_sweep_inputs
    from repro.kernels.ref import jdob_sweep_ref
    profile = mobilenet_v2_profile()
    edge = make_edge_profile(profile)
    fleet = make_fleet(M, profile, edge, beta=(0.0, 10.0), seed=3)
    got = jdob_sweep_op(profile, fleet, edge, interpret=interpret)
    want = jdob_sweep_ref(profile, fleet, edge)
    finite = np.isfinite(want)
    _check((np.isfinite(got) == finite).all(), "sweep: same finite cells")
    _check(finite.any(), "sweep: some cell is feasible")
    np.testing.assert_allclose(got[finite], want[finite], rtol=1e-4)
    _check(np.argmin(got) == np.argmin(want), "sweep: same argmin")
    line = (f"[sweep] M={M}: grid {got.shape}, {int(finite.sum())} finite "
            f"cells match the oracle (rtol 1e-4), argmin "
            f"{np.unravel_index(np.argmin(got), got.shape)}")
    if not interpret:
        hlo = jax.jit(functools.partial(jdob_sweep_kernel, interpret=False)
                      ).lower(*jdob_sweep_inputs(profile, fleet, edge)
                              ).compile().as_text()
        _check("tpu_custom_call" in hlo, "sweep: compiled to a Mosaic call")
        line += ", HLO holds tpu_custom_call"
    print(line)


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() if dev is not None else None
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use']} B"


def phase_served(argv=SERVE_ARGV, dev=None) -> None:
    """The served co-inference path, offline then online, through the
    serving CLI (which checks the co-inference logits against the
    monolithic forward)."""
    from repro.launch import serve
    for mode in ("offline", "online"):
        out = serve.main(list(argv) + (["--online"] if mode == "online"
                                       else []))
        batches = out["batches"]
        print(f"[served] {mode}: partitions={out['partitions']} "
              f"batches={batches} max|dlogit|={out['err']!r} "
              f"peak_bytes_in_use={_peak_bytes(dev)}")
        _check(max(batches) >= 2,
               f"served {mode}: an offloaded batch of >= 2 users")
        gc.collect()


def main() -> int:
    import collections

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/") else None)
    print(f"[cache] persistent compilation cache at {enable_compile_cache()}")
    cache_line = lambda where: print(
        f"[cache] {where}: hits={cache_events['cache_hits']} "
        f"misses={cache_events['cache_misses']}")
    phase_device()
    phase_planner()
    cache_line("after the planner phase")
    phase_online()
    phase_sweep()
    phase_served(dev=dev)
    cache_line("in all")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
