"""Plain J-DOB (paper Alg. 1 + 2), its optimal-grouping DP, the online
flush policies, and the cost model that scores a plan.

Written from the paper's equations in numpy, evaluated on the whole
(ñ, f_e) grid at once.  Every array is held in ``dtype``: float64 for the
reference, a narrower type for the control.  Energies are summed in that
type too and returned as Python floats.

The greedy batching set under f_e is the suffix of the user order that
starts at the first user whose Eq. 18 threshold f_e meets (a descending
sweep moves Alg. 2's pointer exactly there).  Ties keep the earliest
candidate: partitions ascending, frequencies descending, then sort keys in
the order given; all-local wins ties against offloading.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.deploy import Edge, Profile, subset


@dataclasses.dataclass
class Plan:
    energy: float
    partition: int              # ñ; N means every user runs locally
    f_edge: float               # Hz
    offload: np.ndarray         # (M,) bool
    f_device: np.ndarray        # (M,) Hz
    t_end: float                # Eq. 22, relative to the flush


def _local(p: Profile, fl: dict, dt):
    vN, uN = dt(p.v()[-1]), dt(p.u()[-1])
    f = np.clip(fl["zeta"] * vN / fl["deadline"], fl["f_min"], fl["f_max"])
    return f, fl["kappa"] * uN * f * f


def jdob(p: Profile, edge: Edge, fl: dict, t_free: float, sweep: np.ndarray,
         sort_keys=("gamma",), dtype=np.float64) -> Plan:
    """Least-energy plan for one batch (``sort_keys`` > 1: the J-DOB+
    portfolio of user orderings)."""
    dt = np.dtype(dtype).type
    fl = {k: np.asarray(v, dtype) for k, v in fl.items()}
    sweep = np.asarray(sweep, dtype)
    t_free = dt(t_free)
    v, u, O = (np.asarray(x, dtype) for x in (p.v(), p.u(), p.O))
    phi_b, phi_s = (np.asarray(x, dtype) for x in edge.phi_coeffs(p))
    psi_b, psi_s = (np.asarray(x, dtype) for x in edge.psi_coeffs(p))
    M = len(fl["zeta"])
    zeta, kappa, rate = fl["zeta"], fl["kappa"], fl["rate"]
    fmin, fmax, T = fl["f_min"], fl["f_max"], fl["deadline"]
    f_loc, e_loc = _local(p, fl, dt)
    e_all_local = e_loc.sum(dtype=dtype)
    best = None
    for key in sort_keys:
        bk = None
        for nt in range(p.N):
            gamma = O[nt] / rate + zeta * v[nt] / fmax            # Eq. 17
            order_key = {"gamma": -gamma, "budget": T - gamma,
                         "energy": e_loc}[key]
            order = np.argsort(order_key, kind="stable")
            suffT = np.minimum.accumulate(T[order][::-1])[::-1]
            denom = suffT - gamma[order]
            phi_i = phi_b[nt] + phi_s[nt] * np.arange(M, 0, -1).astype(dtype)
            th = np.full(M, np.inf, dtype)
            pos = denom > 0
            th[pos] = phi_i[pos] / denom[pos]                     # Eq. 18
            ok = th[None, :] <= sweep[:, None]                    # (K, M)
            j = np.where(ok.any(1), ok.argmax(1), M)
            has = j < M
            jc = np.minimum(j, M - 1)
            B = np.where(has, M - j, 0).astype(dtype)
            l_o = suffT[jc]                                       # Eq. 10
            phi = phi_b[nt] + phi_s[nt] * B
            psi = psi_b[nt] + psi_s[nt] * B
            gpu_ok = sweep * (l_o - t_free) >= phi                # Eq. 6
            rank = np.empty(M, np.int64)
            rank[order] = np.arange(M)
            off = rank[None, :] >= j[:, None]
            slack = (l_o[:, None] - (O[nt] / rate)[None, :]
                     - (phi / sweep)[:, None])
            gam = np.full(slack.shape, np.inf, dtype)
            sp = slack > 0
            gam[sp] = (zeta[None, :] * v[nt] / np.where(sp, slack, dt(1)))[sp]
            f_dev = np.where(off, np.clip(gam, fmin, fmax), f_loc)  # Eq. 20
            dev_ok = np.where(off, gam <= fmax * dt(1 + 1e-9), True).all(1)
            e_user = np.where(off, kappa * u[nt] * f_dev * f_dev
                              + O[nt] / rate * fl["p_up"], e_loc)
            E = e_user.sum(1, dtype=dtype) + np.where(has, psi * sweep * sweep,
                                                      dt(0))     # Eq. 21
            E = np.where(has & gpu_ok & dev_ok, E, dt(np.inf))
            k = int(np.argmin(E))
            if bk is None or E[k] < bk[0]:
                bk = (E[k], nt, k, off[k], f_dev[k], phi[k])
        if best is None or bk[0] < best[0]:
            best = bk
    E, nt, k, off, f_dev, phi = best
    if not np.isfinite(E) or e_all_local <= E:
        return Plan(float(e_all_local), p.N, float(edge.f_max),
                    np.zeros(M, bool), f_loc.astype(np.float64),
                    float(t_free))
    t_up = zeta * v[nt] / f_dev + O[nt] / rate
    t_end = max(t_free, t_up[off].max()) + phi / sweep[k]         # Eq. 22
    return Plan(float(E), nt, float(sweep[k]), off.copy(),
                f_dev.astype(np.float64), float(t_end))


def evaluate(p: Profile, edge: Edge, fl: dict, t_free: float, partition: int,
             offload, f_device, f_edge: float):
    """Score a plan in float64: its energy (Eq. 21), its GPU end (Eq. 22)
    and its worst deadline excess, max over users of (finish − T)/T, with
    frequencies outside their ranges counted as excess too."""
    off = np.asarray(offload, bool)
    f = np.asarray(f_device, np.float64)
    v, u, O = p.v(), p.u(), p.O
    T = fl["deadline"]
    over = np.maximum(np.maximum(f / fl["f_max"] - 1, fl["f_min"] / f - 1),
                      0).max(initial=0.0)
    loc = ~off
    e = np.where(loc, fl["kappa"] * u[-1] * f * f, 0.0)
    finish = np.where(loc, fl["zeta"] * v[-1] / f, -np.inf)
    t_end = float(t_free)
    if off.any():
        nt, B = int(partition), int(off.sum())
        phi_b, phi_s = edge.phi_coeffs(p)
        psi_b, psi_s = edge.psi_coeffs(p)
        t_up = fl["zeta"] * v[nt] / f + O[nt] / fl["rate"]
        t_end = max(t_free, t_up[off].max()) + (phi_b[nt] + phi_s[nt] * B) / f_edge
        e = np.where(off, fl["kappa"] * u[nt] * f * f
                     + O[nt] / fl["rate"] * fl["p_up"], e)
        e_edge = (psi_b[nt] + psi_s[nt] * B) * f_edge ** 2
        finish = np.where(off, t_end, finish)
        over = max(over, f_edge / edge.f_max - 1, edge.f_min / f_edge - 1, 0)
    else:
        e_edge = 0.0
    excess = max(float(((finish - T) / T).max()), float(over))
    return float(e.sum() + e_edge), t_end, excess


def grouping(p: Profile, edge: Edge, fl: dict, sweep, sort_keys=("gamma",),
             t_free: float = 0.0, dtype=np.float64):
    """The optimal-grouping prefix DP over the deadline-sorted fleet, the
    GPU's residual occupancy threaded from group to group (Eq. 22).
    Returns (energy, groups as lists of fleet indices, per-group plans)."""
    order = np.argsort(fl["deadline"], kind="stable")
    sfl = subset(fl, order)
    M = len(order)
    memo: dict = {}

    def solve(i, j, tf):
        if (i, j, tf) not in memo:
            memo[i, j, tf] = jdob(p, edge, subset(sfl, np.arange(i, j)), tf,
                                  sweep, sort_keys, dtype)
        return memo[i, j, tf]

    dp = [(0.0, float(t_free), -1)]
    for j in range(1, M + 1):
        best = (np.inf, None, 0)
        for i in range(j):
            e_i, tf_i, _ = dp[i]
            if not np.isfinite(e_i):
                continue
            s = solve(i, j, tf_i)
            if e_i + s.energy < best[0]:
                best = (e_i + s.energy, s.t_end, i)
        dp.append(best)
    chain, j = [], M
    while j > 0:
        chain.append((dp[j][2], j))
        j = dp[j][2]
    chain.reverse()
    total, tf, groups, plans = 0.0, float(t_free), [], []
    for i, j in chain:
        s = solve(i, j, tf)
        total += s.energy
        groups.append(order[i:j].tolist())
        plans.append(s)
        tf = s.t_end
    return total, groups, plans


def replay_policy(times, rel_deadlines, l_min, policy: str, keep_frac: float,
                  window: float, n_flushes: int):
    """The online flush rule over an arrival sequence (time order): returns
    the first ``n_flushes`` flushes as (time, first, end, late), the batch
    being requests [first, end).  A request is late when its remaining
    budget at the flush is under its least local latency."""
    out, first, i, n = [], 0, 0, len(times)
    t_pol = None
    while len(out) < n_flushes:
        if first == i:                          # empty queue: take one
            if i == n:
                break
            i += 1
            t_pol = None
        if t_pol is None or policy == "immediate":
            t_pol = _policy(times, rel_deadlines, l_min, policy, keep_frac,
                            window, first, i)
        if i < n and times[i] <= t_pol:
            if policy == "slack":
                t_pol = min(t_pol, times[i] + (1.0 - keep_frac)
                            * rel_deadlines[i])
            elif policy == "lastcall":
                t_pol = min(t_pol, times[i] + rel_deadlines[i]
                            - l_min[i] - 1e-6)
            i += 1
            continue
        if i == n:
            break                   # the next arrival is unknown: stop
        now = max(t_pol, times[i - 1])
        rel = times[first:i] + rel_deadlines[first:i] - now
        late = int(np.sum(rel < l_min[first:i] - 1e-12))
        out.append((now, first, i, late))
        first = i
        t_pol = None
    return out


def _policy(times, rel, l_min, policy, keep_frac, window, a, b):
    if policy == "immediate":
        return times[b - 1]
    if policy == "window":
        return times[a] + window
    if policy == "slack":
        return float(np.min(times[a:b] + (1.0 - keep_frac) * rel[a:b]))
    if policy == "lastcall":
        return float(np.min(times[a:b] + rel[a:b] - l_min[a:b])) - 1e-6
    raise ValueError(f"unknown policy {policy!r}")
