"""Benchmark harness — one function per paper table/figure.

CSV output: ``table,name,us_per_call,derived...`` where `derived` carries
the figure's metric (energy per user, % saving vs LC, roofline seconds).

  fig3   — edge batch profiling curves (latency / energy vs batch size)
  fig4a  — identical deadline β=2.13: avg energy/user vs M, all strategies
  fig4b  — identical deadline β=30.25
  fig5a  — different deadlines, M=10, β ranges, OG outer grouping
  fig5b  — different deadlines, M=20
  complexity — J-DOB wall time vs M (the O(kNM logM) claim)
  beyond — J-DOB+ budget-ordering gain over faithful J-DOB
  roofline   — §Roofline terms from the dry-run artifact (if present)

Run:  PYTHONPATH=src python -m benchmarks.run [table ...]
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from repro.core import (STRATEGIES, jdob_plus, jdob_schedule, local_computing,
                        make_edge_profile, make_fleet, mobilenet_v2_profile,
                        optimal_grouping, single_group)

PROF = mobilenet_v2_profile()
EDGE = make_edge_profile(PROF)
_REPEATS = int(os.environ.get("BENCH_REPEATS", "20"))
_MS = [1, 2, 3, 4, 5, 6, 8, 10, 12, 15, 20, 25, 30]


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def fig3() -> None:
    for b in (1, 2, 4, 8, 16, 32, 64, 128):
        lat = EDGE.batch_latency(PROF, 0, b, EDGE.f_max)
        en = EDGE.batch_energy(PROF, 0, b, EDGE.f_max)
        print(f"fig3,batch_{b},0,lat_ms={lat * 1e3:.3f},energy_J={en:.4f},"
              f"lat_per_sample_ms={lat / b * 1e3:.3f},"
              f"energy_per_sample_J={en / b:.4f}")


def _identical(name: str, beta: float) -> None:
    for M in _MS:
        fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=0)
        row = {}
        us = {}
        for sname, strat in STRATEGIES.items():
            sched, t_us = _timed(strat, PROF, fleet, EDGE)
            row[sname] = sched.energy / M
            us[sname] = t_us
        lc = row["LC"]
        print(f"{name},M_{M},{us['J-DOB']:.0f}," + ",".join(
            f"{sname}={row[sname]:.5f}" for sname in STRATEGIES) +
            f",jdob_saving_pct={100 * (1 - row['J-DOB'] / lc):.2f}")


def fig4a() -> None:
    _identical("fig4a", 2.13)


def fig4b() -> None:
    _identical("fig4b", 30.25)


def _different(name: str, M: int) -> None:
    ranges = [(4.5, 5.5), (2.0, 8.0), (0.0, 10.0)]
    for lo, hi in ranges:
        acc = {s: 0.0 for s in STRATEGIES}
        t_us_total = 0.0
        for rep in range(_REPEATS):
            fleet = make_fleet(M, PROF, EDGE, beta=(lo, hi), seed=rep)
            for sname, strat in STRATEGIES.items():
                if sname == "LC":
                    g = single_group(PROF, fleet, EDGE,
                                     inner=local_computing)
                else:
                    g, t_us = _timed(optimal_grouping, PROF, fleet, EDGE,
                                     inner=strat)
                    if sname == "J-DOB":
                        t_us_total += t_us
                acc[sname] += g.energy / M
        lc = acc["LC"] / _REPEATS
        print(f"{name},beta_{lo}-{hi},{t_us_total / _REPEATS:.0f}," +
              ",".join(f"{s}={acc[s] / _REPEATS:.5f}" for s in STRATEGIES) +
              f",jdob_saving_pct="
              f"{100 * (1 - acc['J-DOB'] / _REPEATS / lc):.2f}")


def fig5a() -> None:
    _different("fig5a", 10)


def fig5b() -> None:
    _different("fig5b", 20)


def complexity() -> None:
    """J-DOB runtime scaling in M (paper: O(k·N·M·logM))."""
    jdob_schedule(PROF, make_fleet(2, PROF, EDGE, beta=5.0, seed=0), EDGE)
    for M in (1, 2, 5, 10, 20, 50, 100, 200):
        fleet = make_fleet(M, PROF, EDGE, beta=(0.0, 10.0), seed=0)
        ts = []
        for _ in range(3):
            _, t_us = _timed(jdob_schedule, PROF, fleet, EDGE)
            ts.append(t_us)
        print(f"complexity,M_{M},{min(ts):.0f},per_user_us={min(ts) / M:.1f}")


def beyond_paper() -> None:
    """J-DOB+ (budget ordering) vs faithful J-DOB on heterogeneous groups."""
    wins = 0
    tot_gain = 0.0
    n = 50
    for rep in range(n):
        fleet = make_fleet(8, PROF, EDGE, beta=(0.0, 10.0), seed=rep)
        a = jdob_schedule(PROF, fleet, EDGE)
        b = jdob_plus(PROF, fleet, EDGE)
        if b.energy < a.energy * (1 - 1e-9):
            wins += 1
        tot_gain += 1 - b.energy / a.energy
    print(f"beyond,jdob_plus_vs_jdob,0,win_rate={wins / n:.2f},"
          f"mean_gain_pct={100 * tot_gain / n:.3f}")


def roofline() -> None:
    path = os.path.join(os.path.dirname(__file__), "results", "roofline.csv")
    if not os.path.exists(path):
        print("roofline,missing,0,run benchmarks/roofline.py first")
        return
    with open(path) as f:
        for line in f:
            print("roofline," + line.strip())


TABLES = dict(fig3=fig3, fig4a=fig4a, fig4b=fig4b, fig5a=fig5a, fig5b=fig5b,
              complexity=complexity, beyond=beyond_paper, roofline=roofline)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or list(TABLES)
    print("table,name,us_per_call,derived")
    for n in names:
        TABLES[n]()




def ablations() -> None:
    """Beyond-paper sensitivity: sweep-granularity ρ, uplink bandwidth,
    and edge batch-amortization strength."""
    M, beta = 10, 5.0
    base_fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=0)
    lc = local_computing(PROF, base_fleet, EDGE).energy
    # ρ: coarser sweeps trade energy for scheduler speed
    for rho_ghz in (0.005, 0.03, 0.1, 0.3):
        s, t_us = _timed(jdob_schedule, PROF, base_fleet, EDGE,
                         rho=rho_ghz * 1e9)
        print(f"ablation,rho_{rho_ghz}GHz,{t_us:.0f},"
              f"saving_pct={100 * (1 - s.energy / lc):.2f}")
    # uplink bandwidth: offloading collapses to local when the link starves
    for bw_mhz in (0.3, 1.0, 3.0, 10.0, 30.0):
        fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=0,
                           bandwidth_hz=bw_mhz * 1e6)
        s = jdob_schedule(PROF, fleet, EDGE)
        lcb = local_computing(PROF, fleet, EDGE).energy
        print(f"ablation,uplink_{bw_mhz}MHz,0,"
              f"saving_pct={100 * (1 - s.energy / lcb):.2f},"
              f"partition={s.partition},batch={s.batch_size}")
    # batch-amortization strength (Fig. 3 startup ratio)
    from repro.core import make_edge_profile
    for startup in (1.0, 4.0, 8.0, 16.0):
        edge = make_edge_profile(PROF, batch_startup=startup,
                                 energy_startup=startup)
        fleet = make_fleet(M, PROF, edge, beta=beta, seed=0)
        s = jdob_schedule(PROF, fleet, edge)
        lcb = local_computing(PROF, fleet, edge).energy
        print(f"ablation,batch_amortization_{startup}x,0,"
              f"saving_pct={100 * (1 - s.energy / lcb):.2f},"
              f"batch={s.batch_size},f_e={s.f_edge / 1e9:.2f}GHz")


TABLES["ablations"] = ablations


def online() -> None:
    """Beyond-paper: online arrivals (the paper's §V future work) — energy
    vs arrival rate per flush policy, against the clairvoyant oracle."""
    from repro.core import (all_local_energy, oracle_bound,
                            poisson_arrivals, simulate_online)
    M, beta = 12, 20.0
    fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=0)
    for rate in (10.0, 50.0, 200.0, 1000.0):
        accs = {p: 0.0 for p in ("immediate", "window", "slack", "lastcall")}
        lc_t = orc_t = 0.0
        reps = 5
        for seed in range(reps):
            arr = poisson_arrivals(M, rate, fleet, seed=seed)
            lc_t += all_local_energy(arr, PROF, fleet, EDGE)
            orc_t += oracle_bound(arr, PROF, fleet, EDGE)
            for p in accs:
                accs[p] += simulate_online(arr, PROF, fleet, EDGE,
                                           policy=p, window=0.02).energy
        print(f"online,rate_{rate:.0f}Hz,0,LC={lc_t / reps:.4f},"
              f"oracle={orc_t / reps:.4f}," +
              ",".join(f"{p}={accs[p] / reps:.4f}" for p in accs) +
              f",slack_vs_oracle_pct="
              f"{100 * (accs['slack'] / orc_t - 1):.1f}")


TABLES["online"] = online


def tpu_edge() -> None:
    """DESIGN.md §3.2: the TPU-v5e analytic edge profile (weight streaming
    + dispatch overhead + MXU compute) with phone-vs-TPU calibration
    (α=40: 40× slower locally; η=0.015: ~2 W vs ~130 W)."""
    from repro.core import make_tpu_v5e_edge_profile
    v5e = make_tpu_v5e_edge_profile(PROF, param_bytes=3.4e6 * 2)
    for M in (2, 8, 16):
        fleet = make_fleet(M, PROF, v5e, beta=10.0, alpha=40.0, eta=0.015,
                           seed=0)
        lc = local_computing(PROF, fleet, v5e).energy
        s = jdob_schedule(PROF, fleet, v5e)
        print(f"tpu_edge,M_{M},0,LC={lc / M:.5f},JDOB={s.energy / M:.5f},"
              f"saving_pct={100 * (1 - s.energy / lc):.1f},"
              f"partition={s.partition},batch={s.batch_size},"
              f"f_e={s.f_edge / 1e9:.2f}GHz")


TABLES["tpu_edge"] = tpu_edge

if __name__ == "__main__":
    main()
