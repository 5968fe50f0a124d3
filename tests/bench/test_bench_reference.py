"""The references agree with the program at small sizes, and the
yardstick imports nothing of the program."""
import ast
import json

import benchtest_util
import numpy as np
import pytest

from bench import deploy, traffic, weights
from bench.reference import planner as ref
from bench.reference import transformer as tf

ROOT = benchtest_util.ROOT
MINI = json.loads(
    (ROOT / "bench/configs/minitron4b-coinf.json").read_text())["model"]
#: the harness modules that touch the program; everything else in bench/
#: is the yardstick
HARNESS = {"sut.py", "run.py", "control.py"}
EDGE = dict(f_min=0.2e9, f_max=2.1e9, lat_b1=4e-3, batch_startup=8.0,
            energy_b1=0.35, energy_startup=8.0)
FLEET = dict(alpha=1.0, eta=0.6, snr_db=30.0, bandwidth_hz=10e6, p_up=1.0,
             f_min=1.5e9, f_max=2.6e9)


def test_yardstick_imports_nothing_of_the_program():
    for path in (ROOT / "bench").rglob("*.py"):
        if path.name in HARNESS:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            mods = ([a.name for a in node.names] if isinstance(node, ast.Import)
                    else [node.module or ""] if isinstance(node, ast.ImportFrom)
                    else [])
            assert not any(m.split(".")[0] == "repro" for m in mods), path


def test_deployment_arithmetic_is_the_programs():
    from repro.configs import ARCHS
    from repro.core import (make_edge_profile, make_fleet,
                            mobilenet_v2_profile, profile_from_arch)
    import json
    model = json.loads((ROOT / "bench/configs/minitron4b-coinf.json"
                        ).read_text())["model"]
    for mine, theirs in ((deploy.mobilenet_v2_profile(),
                          mobilenet_v2_profile()),
                         (deploy.decoder_prefill_profile(model, 32),
                          profile_from_arch(ARCHS["minitron-4b"], seq=32))):
        np.testing.assert_array_equal(mine.A, theirs.A)
        np.testing.assert_array_equal(mine.O, theirs.O)
        e, pe = deploy.edge_profile(mine, EDGE), make_edge_profile(theirs)
        for k in ("delta0", "delta1", "eps0", "eps1"):
            np.testing.assert_array_equal(getattr(e, k), getattr(pe, k))
        beta = np.linspace(0.0, 10.0, 7)
        fl, pf = deploy.fleet(mine, e, FLEET, beta), make_fleet(7, theirs, pe,
                                                                 beta=beta)
        for k in ("zeta", "kappa", "f_min", "f_max", "rate", "p_up",
                  "deadline"):
            np.testing.assert_array_equal(fl[k], getattr(pf, k))


@pytest.mark.parametrize("M,lo,hi,t_free,seed", [
    (1, 10, 30, 0.0, 0), (12, 0, 10, 0.0, 1), (40, 0, 10, 3e-3, 2),
    (150, 10, 30, 1e-3, 3)])
def test_planner_matches_the_program(M, lo, hi, t_free, seed):
    from repro.core import jdob_plus, jdob_schedule
    from bench import sut
    P = deploy.mobilenet_v2_profile()
    E = deploy.edge_profile(P, EDGE)
    fl = deploy.fleet(P, E, FLEET, np.random.default_rng(seed).uniform(
        lo, hi, M))
    sweep = deploy.f_sweep(E, 0.03e9)
    for keys, inner in ((("gamma",), jdob_schedule),
                        (("gamma", "budget", "energy"), jdob_plus)):
        got = inner(sut.program_profile(P), sut.program_fleet(fl),
                    sut.program_edge(E), t_free=t_free)
        want = ref.jdob(P, E, fl, t_free, sweep, keys)
        assert got.energy == pytest.approx(want.energy, rel=2e-6)
        e, t_end, excess = ref.evaluate(P, E, fl, t_free, got.partition,
                                        got.offload, got.f_device, got.f_edge)
        assert e == pytest.approx(want.energy, rel=2e-6)
        assert excess < 1e-6


def test_grouping_matches_the_program():
    from repro.core import PlannerService
    from bench import sut
    P = deploy.mobilenet_v2_profile()
    E = deploy.edge_profile(P, EDGE)
    fl = deploy.fleet(P, E, FLEET, traffic.wave_betas(
        {"mode": "waves", "wave_users": 16, "beta": [0, 10]}, 5, 0))
    svc = PlannerService(sut.program_profile(P), sut.program_edge(E))
    got = svc.plan_fleet(sut.program_fleet(fl))
    want, groups, _ = ref.grouping(P, E, fl, deploy.f_sweep(E, 0.03e9))
    assert got.energy == pytest.approx(want, rel=2e-6)
    assert sorted(sum(groups, [])) == list(range(16))
    svc.close()


def test_policy_replay_matches_the_scheduler():
    from repro.core import OnlineArrival, OnlineScheduler
    from bench import sut
    P = deploy.mobilenet_v2_profile()
    E = deploy.edge_profile(P, EDGE)
    mix = {"mode": "online", "devices": 50, "beta": [10, 30],
           "rate_hz": 2000.0, "hold_frac": 0.3, "block": 400}
    fl = deploy.fleet(P, E, FLEET, traffic.device_betas(mix, 9))
    blk = traffic.OnlineStream(mix, fl["deadline"], 9).next_block()
    sched = OnlineScheduler(sut.program_profile(P), sut.program_fleet(fl),
                            sut.program_edge(E), policy="slack",
                            keep_frac=0.7)
    for i, (t, d) in enumerate(zip(blk.times, blk.devices)):
        sched.submit(OnlineArrival(int(d), float(t), float(fl["deadline"][d]),
                                   payload=i))
    sched.run_batched()
    l_min = fl["zeta"] * P.v()[-1] / fl["f_max"]
    evs = sched.flushes[:-3]                 # the tail drains with no next
    replay = ref.replay_policy(blk.times, fl["deadline"][blk.devices],
                               l_min[blk.devices], "slack", 0.7, 0.0,
                               len(evs))
    assert len(replay) == len(evs) > 5
    for ev, (t, a, b, late) in zip(evs, replay):
        assert ev.time == t
        assert [x.payload for x in ev.arrivals] == list(range(a, b))
        assert ev.violations == late


def test_forward_matches_the_executor():
    import jax.numpy as jnp
    from repro.serving.engine import BlockwiseExecutor
    from bench import sut
    model = dict(MINI, arch="tiny", num_layers=3, d_model=128, num_heads=4,
                 num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512)
    w = weights.make(model, 2 ** 33 + 5)
    ex = BlockwiseExecutor(sut.arch_config(model), w)
    tok = np.random.default_rng(0).integers(0, 512, (3, 32)).astype(np.int32)
    got = np.asarray(ex.full_forward(jnp.asarray(tok)))
    want = np.asarray(tf.logits(w, tf.hidden(w, tok, model), model))
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
    # the weights are the seed's: same seed same leaves, another seed not
    w2 = weights.make(model, 2 ** 33 + 5)
    assert np.array_equal(np.asarray(w2["lm_head"]["w"]),
                          np.asarray(w["lm_head"]["w"]))
    w3 = weights.make(model, 6)
    assert not np.array_equal(np.asarray(w3["lm_head"]["w"]),
                              np.asarray(w["lm_head"]["w"]))


@pytest.mark.parametrize("key,value", [
    ("norm", "layer"), ("mlp_activation", "no-such-activation"),
    ("rotary_fraction", 0.5), ("compute_dtype", "bfloat16"),
    ("matmul_precision", "high")])
def test_stated_equations_the_program_does_not_compute_are_refused(key,
                                                                   value):
    """A configuration that states equations or arithmetic the executor
    does not implement is refused, not served and checked as something
    else."""
    from bench import sut
    with pytest.raises(ValueError, match=key):
        sut.arch_config(dict(MINI, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("norm", "layer"), ("mlp_activation", "no-such-activation"),
    ("rotary_fraction", 0.5), ("matmul_precision", "bfloat16"),
    ("plan", [[[{"kind": "swa", "ffn": "dense", "window": 8}], 1]])])
def test_reference_refuses_equations_it_does_not_implement(key, value):
    model = dict(MINI, num_layers=1, d_model=128, num_heads=4,
                 num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512)
    w = weights.make(model, 3)
    with pytest.raises(ValueError, match=key):
        tf.hidden(w, np.zeros((1, 4), np.int32), dict(model, **{key: value}))


def test_arch_config_of_minitron_is_the_one_segment_dense_model():
    """No ``plan`` in the file: the one dense segment the harness always
    built, field for field."""
    from repro.configs.base import ArchConfig
    from bench import sut
    want = ArchConfig(name="minitron-4b", family="dense",
                      source="bench configuration", num_layers=32,
                      d_model=3072, num_heads=24, num_kv_heads=8,
                      head_dim=128, d_ff=9216, vocab_size=256000,
                      gated_mlp=False, rope_theta=1e6, norm_eps=1e-5)
    assert sut.arch_config(MINI) == want


def test_arch_config_builds_the_stated_plan():
    from repro.configs.base import LayerSpec
    from bench import sut
    model = dict(MINI, num_layers=3, plan=[
        [[{"kind": "attn", "ffn": "dense", "window": None}], 1],
        [[{"kind": "swa", "ffn": "dense", "window": 8}], 2]])
    cfg = sut.arch_config(model)
    assert cfg.layer_sequence() == [LayerSpec("attn", "dense"),
                                     LayerSpec("swa", "dense", 8),
                                     LayerSpec("swa", "dense", 8)]


def test_the_programs_own_table_is_read_where_it_has_one(monkeypatch):
    """A program that states what its executor computes, next to
    ``BlockwiseExecutor``, is held to that table and not to the harness's
    record of it."""
    import repro.serving.engine as engine
    from bench import sut
    model = dict(MINI, mlp_activation="no-such-activation")
    with pytest.raises(ValueError, match="mlp_activation"):
        sut.arch_config(model)
    monkeypatch.setattr(engine, "PROGRAM_ACTIVATION",
                        {False: ("gelu_tanh", "no-such-activation"),
                         True: "silu"}, raising=False)
    assert sut.arch_config(model).name == "minitron-4b"
    monkeypatch.setattr(engine, "PROGRAM", dict(sut.PROGRAM, norm=("layer",)),
                        raising=False)
    with pytest.raises(ValueError, match="norm"):
        sut.arch_config(model)
