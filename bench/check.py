"""The comparison that decides ``correct``.

What the window produced is set against the plain references:

* ``flush_mismatch`` — online cells: flushes whose time, members or late
  count differ from the flush policy replayed over the same arrivals (an
  exact comparison, limit 0).
* ``group_mismatch`` — wave cells: users that a wave's groups do not
  cover exactly once (exact, limit 0).
* ``plan_energy_gap`` — for a seeded sample of flushes (waves): the
  largest of |reported − optimal| and |scored − optimal| over optimal,
  where optimal is the reference planner's energy on the same inputs and
  scored is the program's plan priced by the reference cost model.
* ``deadline_excess`` — the same plans scored: the worst (finish −
  deadline)/deadline of any user, frequencies outside their range, and
  a GPU booking that ends before the plan's GPU work does.
* ``logit_err`` — served cells: max |logit − reference| over the sampled
  requests' logits, over the reference's max |logit|.

Each number is computed the same way for the control (the reference in a
lower precision put in the program's place), see ``bench/control.py``.
"""
from __future__ import annotations

import numpy as np

from bench import deploy, traffic
from bench.reference import model_module
from bench.reference import planner as ref

#: flushes (waves) of a run whose plans are checked against the reference
PLAN_SAMPLE = {"online": 8, "waves": 2}


def sample(n: int, k: int, seed: int, tag: int = 11) -> list:
    """``k`` of ``n`` indices drawn from the seed, always with the last."""
    if n <= k:
        return list(range(n))
    pick = traffic.rng(seed, tag).choice(n - 1, k - 1, replace=False)
    return sorted(pick.tolist()) + [n - 1]


def online(drive_cfg: dict, mix: dict, seed: int, ans: dict,
           control=None, root=None) -> dict:
    """Numbers of an online cell (served or not) from its ``answers()``."""
    P = deploy.task_profile(drive_cfg, root)
    E = deploy.edge_profile(P, drive_cfg["edge"])
    fl = deploy.fleet(P, E, drive_cfg["fleet"],
                      traffic.device_betas(mix, seed))
    sweep = deploy.f_sweep(E, drive_cfg["planner"]["rho"])
    keys = tuple(drive_cfg["planner"]["online"])
    sch = drive_cfg["scheduler"]
    T = fl["deadline"]
    l_min = fl["zeta"] * P.v()[-1] / fl["f_max"]
    devs, times = ans["devices"], ans["times"]
    evs = ans["flushes"]
    replay = ref.replay_policy(times, T[devs], l_min[devs], sch["policy"],
                               sch["keep_frac"], sch.get("window", 0.0),
                               len(evs))
    mismatch = abs(len(replay) - len(evs))
    for ev, (t, a, b, late) in zip(evs, replay):
        if (ev["time"] != t or ev["ids"] != list(range(a, b))
                or ev["late"] != late):
            mismatch += 1
    gap, excess = _plans_online(P, E, fl, sweep, keys, evs, times, seed,
                                control)
    return {"flush_mismatch": float(mismatch), "plan_energy_gap": gap,
            "deadline_excess": excess}


def _plans_online(P, E, fl, sweep, keys, evs, times, seed, control):
    gap, excess, horizon = 0.0, -np.inf, 0.0
    picks = set(sample(len(evs), PLAN_SAMPLE["online"], seed))
    for k, ev in enumerate(evs):
        if k in picks:
            now = ev["time"]
            sub = deploy.subset(fl, np.asarray(ev["users"]))
            sub["deadline"] = times[ev["ids"]] + sub["deadline"] - now
            tf = max(horizon - now, 0.0)
            if control is not None:
                ev = _as_answer(ref.jdob(P, E, sub, tf, sweep, keys, control),
                                now)
            g, x = _score(P, E, sub, tf, sweep, keys, ev)
            gap, excess = max(gap, g), max(excess, x)
            if np.any(ev["offload"]):
                short = (now + _t_end(P, E, sub, tf, ev) - ev["gpu_free"])
                excess = max(excess,
                             short / float(sub["deadline"][ev["offload"]].min()))
        if np.any(ev["offload"]):
            horizon = max(horizon, ev["gpu_free"])
    return gap, excess


def _t_end(P, E, sub, tf, plan) -> float:
    return ref.evaluate(P, E, sub, tf, plan["partition"], plan["offload"],
                        plan["f_device"], plan["f_edge"])[1]


def _as_answer(plan: ref.Plan, now: float) -> dict:
    """A reference plan in the shape of a program answer (the control)."""
    return dict(energy=plan.energy, partition=plan.partition,
                offload=plan.offload, f_device=plan.f_device,
                f_edge=plan.f_edge, gpu_free=now + plan.t_end)


def _score(P, E, sub, tf, sweep, keys, plan):
    """(energy gap, deadline excess) of one plan."""
    best = ref.jdob(P, E, sub, tf, sweep, keys)
    e, _, x = ref.evaluate(P, E, sub, tf, plan["partition"], plan["offload"],
                           plan["f_device"], plan["f_edge"])
    return (max(abs(plan["energy"] - best.energy), abs(e - best.energy))
            / best.energy, x)


def waves(drive_cfg: dict, mix: dict, seed: int, ans: dict,
          control=None, root=None) -> dict:
    P = deploy.task_profile(drive_cfg, root)
    E = deploy.edge_profile(P, drive_cfg["edge"])
    sweep = deploy.f_sweep(E, drive_cfg["planner"]["rho"])
    keys = tuple(drive_cfg["planner"]["waves"])
    ws = ans["waves"]
    mismatch, gap, excess = 0, 0.0, -np.inf
    for w, wave in enumerate(ws):
        n = mix["wave_users"]
        got = np.sort(np.concatenate([np.asarray(g, int)
                                      for g in wave["groups"]]))
        if not np.array_equal(got, np.arange(n)):
            mismatch += n - len(np.intersect1d(got, np.arange(n))) \
                + abs(len(got) - n)
    for w in sample(len(ws), PLAN_SAMPLE["waves"], seed):
        fl = deploy.fleet(P, E, drive_cfg["fleet"],
                          traffic.wave_betas(mix, seed, w))
        best, _, _ = ref.grouping(P, E, fl, sweep, keys)
        wave = ws[w]
        if control is not None:
            e, groups, plans = ref.grouping(P, E, fl, sweep, keys,
                                            dtype=control)
            wave = dict(energy=e, groups=groups,
                        plans=[_as_answer(p, 0.0) for p in plans])
        tf, scored = 0.0, 0.0
        for g, plan in zip(wave["groups"], wave["plans"]):
            sub = deploy.subset(fl, np.asarray(g, int))
            e, t_end, x = ref.evaluate(P, E, sub, tf, plan["partition"],
                                       plan["offload"], plan["f_device"],
                                       plan["f_edge"])
            scored += e
            excess = max(excess, x)
            tf = t_end
        gap = max(gap, abs(wave["energy"] - best) / best,
                  abs(scored - best) / best)
    return {"group_mismatch": float(mismatch), "plan_energy_gap": gap,
            "deadline_excess": excess}


def logits(model: dict, weights, kept: dict, tokens: dict,
           mode: str = "highest", vocab_block: int = 32000,
           root=None) -> dict:
    """Over the kept requests: ``logit_err``, max |Δlogit| over the
    reference's max |logit|, and ``logit_rms``, the root-mean-square
    |Δlogit| over the reference's root-mean-square logit.  ``mode`` other
    than ``"highest"`` scores the reference forward in that arithmetic (the
    control) in place of the kept logits.  The reference is the module the
    configuration's ``model`` names (``bench/reference``)."""
    if not kept:
        return {"logit_err": float("inf"), "logit_rms": float("inf")}
    import jax
    tf = model_module(model, root)
    ids = sorted(kept)
    toks = np.stack([tokens[i] for i in ids])
    got = np.stack([kept[i] for i in ids])
    h = tf.hidden(weights, toks, model)
    h_cand = h if mode == "highest" else tf.hidden(weights, toks, model, mode)
    err, scale, sq_err, sq_ref = 0.0, 0.0, 0.0, 0.0
    V = model["vocab_size"]
    for c0 in range(0, V, vocab_block):
        cols = (c0, min(V, c0 + vocab_block))
        want = np.asarray(jax.device_get(tf.logits(weights, h, model,
                                                   cols=cols)), np.float64)
        cand = got[..., cols[0]:cols[1]] if mode == "highest" else \
            np.asarray(jax.device_get(tf.logits(weights, h_cand, model,
                                                mode, cols)))
        d = cand - want
        err = max(err, float(np.abs(d).max()))
        scale = max(scale, float(np.abs(want).max()))
        sq_err += float(np.sum(d * d))
        sq_ref += float(np.sum(want * want))
    return {"logit_err": err / scale, "logit_rms": float(np.sqrt(sq_err / sq_ref))}
