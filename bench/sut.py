"""The system under test, driven through its normal entry points.

Only this module and ``bench/run.py`` import the program.  A cell is one
of three drives, chosen by its data: a configuration with a ``model``
serves it (``ServedDrive``); without one, an ``online`` mix drives the
event loop and planner (``OnlineDrive``) and a ``waves`` mix the grouping
planner (``WavesDrive``).

Each drive builds the cell from the seed, warms up the shapes its traffic
uses, and then runs flush after flush.  A flush is one
``OnlineScheduler.step_batch()`` (or one ``PlannerService.plan_fleet``
call on a whole wave) and ends with its result on the host; its record
holds its wall time and what the harness measured inside it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import deploy, traffic

#: wave index of the first warm-up wave: far past any window's waves
WARM_WAVE = 2 ** 40

#: planner sort-key portfolios by the program's inner function name
INNERS = {("gamma",): "jdob_schedule",
          ("gamma", "budget", "energy"): "jdob_plus"}


def _inner(sort_keys):
    import repro.core as core
    return getattr(core, INNERS[tuple(sort_keys)])


def program_profile(p: deploy.Profile):
    from repro.core import TaskProfile
    return TaskProfile(p.name, p.A, p.O, p.g, p.q)


def program_edge(e: deploy.Edge):
    from repro.core import EdgeProfile
    return EdgeProfile(e.f_min, e.f_max, e.delta0, e.delta1, e.eps0, e.eps1)


def program_fleet(fl: dict):
    from repro.core import DeviceFleet
    return DeviceFleet(**fl)


#: what the served executor computes, by configuration key, for a program
#: that states no table of its own next to ``BlockwiseExecutor``: a
#: configuration that states anything else cannot be served as stated
PROGRAM = {"norm": ("rms",), "rotary_fraction": (1.0,),
           "compute_dtype": ("float32",), "matmul_precision": ("float32",)}
#: the MLP activation it computes, by ``gated_mlp``: one name or a tuple
PROGRAM_ACTIVATION = {False: "gelu_tanh", True: "silu"}


def program_table():
    """(``PROGRAM``, ``PROGRAM_ACTIVATION``) of the served executor: the
    program's own tables in ``repro.serving.engine`` where it has them, else
    this module's record of what it computes."""
    import repro.serving.engine as engine
    return (getattr(engine, "PROGRAM", PROGRAM),
            getattr(engine, "PROGRAM_ACTIVATION", PROGRAM_ACTIVATION))


def arch_config(model: dict):
    """The program's ``ArchConfig`` of the configuration's model: every
    ``ArchConfig`` field the ``model`` names, and its layer ``plan``, a list
    of ``[[{"kind", "ffn", "window"}, ...], repeats]`` (none: one segment of
    full-attention layers with a dense MLP).  Raises where the configuration
    states what the executor does not compute."""
    from repro.configs.base import ArchConfig, LayerSpec
    computes, activation = program_table()
    for key, ok in computes.items():
        if model[key] not in ok:
            raise ValueError(f"the served executor computes {key} in "
                             f"{ok}, not {model[key]!r}")
    acts = activation[bool(model["gated_mlp"])]
    if model["mlp_activation"] not in (acts if isinstance(acts, tuple)
                                       else (acts,)):
        raise ValueError(f"the served executor has no mlp_activation="
                         f"{model['mlp_activation']!r} with gated_mlp="
                         f"{model['gated_mlp']}")
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in model.items()
          if k in fields - {"name", "source", "plan"}}
    kw.setdefault("family", "dense")
    if "plan" in model:
        kw["plan"] = tuple((tuple(LayerSpec(**e) for e in pattern), reps)
                           for pattern, reps in model["plan"])
    return ArchConfig(name=model["arch"], source="bench configuration", **kw)


@dataclasses.dataclass
class Flush:
    ms: float                   # wall time of the call, result on the host
    n: int                      # requests whose result it delivered
    late: int                   # of them, past their point of no return
    plan_ms: float | None       # steady planner samples taken inside it
    misses: int = 0             # planner compiles inside it
    og_plans: int = 0           # grouping plans inside it
    og_dispatches: int = 0      # grouping-DP device dispatches inside it
    exec_ms: float = 0.0        # model execution inside it (served)
    sizes: tuple = ()           # (local, offloaded) users executed
    traced: bool = False        # inside the profiler's window


class PlannerProbe:
    """Reads ``PlannerStats`` of every planner of a service flush by flush:
    steady samples taken during the flush (a flush in which the stats
    decimated their samples reads ``None``), compiles, and the grouping
    DP's dispatch counters."""

    def __init__(self, service):
        self.service = service
        self._last = self._read()

    def _read(self):
        return {k: (st.plan_calls - st.compile_calls, len(st.plan_ns),
                    st.misses, st.og_plans, st.og_dispatches, st.plan_ns)
                for k, st in self.service.stats_by_planner().items()}

    def delta(self):
        now = self._read()
        plan_ns, exact, misses, og_plans, og_disp = 0, True, 0, 0, 0
        for k, (steady, n, miss, ogp, ogd, samples) in now.items():
            s0, n0, m0, p0, d0, _ = self._last.get(k, (0, 0, 0, 0, 0, []))
            new = steady - s0
            if new and n - n0 == new:
                plan_ns += sum(samples[n0:n])
            elif new:
                exact = False
            misses += miss - m0
            og_plans += ogp - p0
            og_disp += ogd - d0
        self._last = now
        return (plan_ns / 1e6 if exact else None), misses, og_plans, og_disp


def annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class OnlineDrive:
    """The event loop and planner on an ``online`` mix, no model."""

    served = False

    def __init__(self, config: dict, mix: dict, seed: int, root=None):
        from repro.core import PlannerService
        self.config, self.mix, self.seed, self.root = config, mix, seed, root
        self.P = deploy.task_profile(config, root)
        self.E = deploy.edge_profile(self.P, config["edge"])
        self.fl = deploy.fleet(self.P, self.E, config["fleet"],
                               traffic.device_betas(mix, seed))
        self.sort_keys = tuple(config["planner"]["online"])
        self.profile, self.edge = program_profile(self.P), program_edge(self.E)
        self.fleet = program_fleet(self.fl)
        self.T = self.fl["deadline"]
        self.service = PlannerService(self.profile, self.edge,
                                      rho=config["planner"]["rho"])
        self.inner = _inner(self.sort_keys)
        self._build()
        self.sched = self._scheduler(on_flush=self._on_flush)
        self.stream = traffic.OnlineStream(mix, self.T, seed, self._vocab())
        self.blocks: list[traffic.Arrivals] = []
        self.n_sub = self.n_done = 0
        self.t_sub = -np.inf       # arrival time of the last submitted
        self.trace = False
        self.probe = None

    #: what each flush runs once it is booked (the served drive executes it)
    _on_flush = None

    def _build(self) -> None:
        """What a subclass adds before the scheduler exists."""

    def _vocab(self) -> int:
        return 0

    def _scheduler(self, on_flush=None):
        from repro.core import OnlineScheduler
        s = self.config["scheduler"]
        return OnlineScheduler(self.profile, self.fleet, self.edge,
                               policy=s["policy"], keep_frac=s["keep_frac"],
                               rho=self.config["planner"]["rho"],
                               inner=self.inner, service=self.service,
                               on_flush=on_flush)

    # ---- traffic ----------------------------------------------------------
    def _times(self, i: int) -> float:
        b, r = divmod(i, self.stream.block)
        while b >= len(self.blocks):
            self.blocks.append(self.stream.next_block())
        return float(self.blocks[b].times[r])

    def _submit_through(self, sched, t_need: float) -> None:
        """Submit arrivals, in order, until one lies past ``t_need``."""
        from repro.core import OnlineArrival
        T = self.T
        while self.t_sub <= t_need:
            i = self.n_sub
            t = self._times(i)
            dev = int(self.blocks[i // self.stream.block].devices[
                i % self.stream.block])
            sched.submit(OnlineArrival(dev, t, float(T[dev]), payload=i))
            self.n_sub += 1
            self.t_sub = t

    def feed(self, sched, n_done: int) -> None:
        """Keep the scheduler's arrival heap a whole deadline ahead of the
        earliest request not yet flushed (or of the scheduler's clock, if
        that is later): the next flush can be no later."""
        with annotate(self.trace, "bench.submit"):
            self._submit_through(sched, max(self._times(n_done), sched.now)
                                 + self.T.max())

    # ---- warm-up ------------------------------------------------------------
    def max_batch(self) -> int:
        """The largest batch the policy can flush: arrivals within one
        slack window of the first, at the mix's rate with room for its
        gaps, and never more than the fleet."""
        k = self.config["scheduler"]["keep_frac"]
        n = int(np.ceil(2.0 * self.mix["rate_hz"] * (1.0 - k) * self.T.max()))
        return max(1, min(self.mix["devices"], n))

    def warm(self) -> None:
        """Compile the planner's batch buckets this traffic can flush."""
        from repro.core.jdob import _bucket
        planner = self.service.planner_for(self.inner)
        b, top = planner.min_user_bucket, _bucket(self.max_batch(),
                                                  planner.min_user_bucket)
        while b <= top:
            sub = self.fleet.subset(np.arange(min(b, self.fleet.M)))
            planner.plan([sub], [0.0])
            b *= 2
        self._warm_loop()

    def _warm_loop(self, flushes: int = 3) -> None:
        """A few flushes of this cell's own traffic through a scheduler of
        their own, so the event loop's host paths are warm too."""
        sched = self._scheduler(on_flush=self._warm_hook())
        saved = (self.n_sub, self.t_sub)
        self.n_sub, self.t_sub = 0, -np.inf
        done = 0
        for _ in range(flushes):
            self.feed(sched, done)
            ev = sched.step_batch()
            done += len(ev.arrivals)
        self.n_sub, self.t_sub = saved

    def _warm_hook(self):
        return None

    # ---- the window ---------------------------------------------------------
    def start(self, trace: bool) -> None:
        self.trace = trace
        self.probe = PlannerProbe(self.service)

    def flush(self) -> Flush:
        self.feed(self.sched, self.n_done)
        self._exec_ms, self._sizes = 0.0, ()
        t0 = time.perf_counter()
        with annotate(self.trace, "bench.drain"):
            ev = self.sched.step_batch()
        ms = (time.perf_counter() - t0) * 1e3
        self.n_done += len(ev.arrivals)
        return Flush(ms, len(ev.arrivals), ev.violations, *self.probe.delta(),
                     exec_ms=self._exec_ms, sizes=self._sizes)

    # ---- what the check reads ----------------------------------------------
    def answers(self) -> dict:
        """The window's flushes as the program booked them."""
        evs = self.sched.flushes
        self._times(self.n_done + 1)        # the arrivals that closed them
        times = np.concatenate([b.times for b in self.blocks])
        devs = np.concatenate([b.devices for b in self.blocks])
        return dict(flushes=[dict(time=ev.time,
                                  ids=[a.payload for a in ev.arrivals],
                                  users=np.asarray(ev.users),
                                  late=ev.violations, gpu_free=ev.gpu_free,
                                  energy=ev.schedule.energy,
                                  partition=ev.schedule.partition,
                                  offload=np.asarray(ev.schedule.offload),
                                  f_device=np.asarray(ev.schedule.f_device),
                                  f_edge=ev.schedule.f_edge)
                             for ev in evs],
                    times=times, devices=devs)

    def close(self) -> None:
        self.sched = None
        self.service.close()


class ServedDrive(OnlineDrive):
    """The served co-inference path: the server's scheduler, each flush
    executed on the model by ``run_partitioned``; a seeded sample of the
    window's finished requests keeps its logits for the check (held by
    reference: nothing is copied inside the window)."""

    served = True
    KEEP = 16

    def __init__(self, config: dict, mix: dict, seed: int, root=None):
        self.model = config["model"]
        super().__init__(config, mix, seed, root)

    def _build(self) -> None:
        from repro.serving import CoInferenceServer
        from bench import weights
        self.params = weights.make(self.model, self.seed, self.root)
        self.server = CoInferenceServer(arch_config(self.model), self.params,
                                        self.profile, self.fleet, self.edge,
                                        inner=self.inner,
                                        rho=self.config["planner"]["rho"],
                                        service=self.service)
        self._on_flush = self._execute
        #: the check's sample: a reservoir of KEEP finished requests drawn
        #: from the seed, each slot (request id, flush output, row)
        self.kept: list = []
        self._seen = 0
        self._pick = traffic.rng(self.seed, 7)
        self._exec_ms, self._sizes = 0.0, ()

    def _vocab(self) -> int:
        return self.model["vocab_size"]

    #: arrivals replayed through the flush rule to size the warm-up: more
    #: than a window serves
    WARM_REPLAY = 8192

    def max_batch(self) -> int:
        """The planner's bound, raised to the largest flush the flush rule
        makes of this seed's first ``WARM_REPLAY`` arrivals, and never more
        than the fleet: every executor shape a flush of the window takes."""
        from bench.reference.planner import replay_policy
        top = super().max_batch()
        if top >= self.mix["devices"]:
            return top
        self._times(self.WARM_REPLAY - 1)
        times = np.concatenate([b.times for b in self.blocks])
        devs = np.concatenate([b.devices for b in self.blocks])
        s = self.config["scheduler"]
        l_min = self.fl["zeta"] * self.P.v()[-1] / self.fl["f_max"]
        flushes = replay_policy(times, self.T[devs], l_min[devs], s["policy"],
                                s["keep_frac"], s.get("window", 0.0),
                                len(times))
        most = max(b - a for _, a, b, _ in flushes)
        return min(self.mix["devices"], max(top, most))

    def _scheduler(self, on_flush=None):
        s = self.config["scheduler"]
        return self.server.scheduler(policy=s["policy"],
                                     keep_frac=s["keep_frac"],
                                     on_flush=on_flush)

    def tokens(self, i: int) -> np.ndarray:
        b, r = divmod(i, self.stream.block)
        self._times(i)
        return self.blocks[b].tokens[r]

    def _run(self, ev):
        from repro.serving import Request
        from repro.serving.server import run_partitioned
        reqs = [Request(user=a.user, tokens=self.tokens(a.payload),
                        deadline=a.rel_deadline) for a in ev.arrivals]
        return run_partitioned(self.server.executor, self.model["vocab_size"],
                               reqs, ev.schedule)

    def _execute(self, ev) -> None:
        t0 = time.perf_counter()
        with annotate(self.trace, "bench.exec"):
            out = self._run(ev)
        self._exec_ms = (time.perf_counter() - t0) * 1e3
        off = int(np.sum(ev.schedule.offload))
        self._sizes = (len(ev.arrivals) - off, off)
        for row, a in enumerate(ev.arrivals):      # reservoir sampling
            if len(self.kept) < self.KEEP:
                self.kept.append((a.payload, out, row))
            else:
                j = int(self._pick.integers(0, self._seen + 1))
                if j < self.KEEP:
                    self.kept[j] = (a.payload, out, row)
            self._seen += 1

    def _warm_hook(self):
        return self._run

    def warm(self) -> None:
        """Compile every executor shape batches of 1..max can take (the
        embedding, the row gathers that split a batch, the layer step of
        each element of the layer plan and the head step), then the planner
        buckets and a few real flushes."""
        import jax
        import jax.numpy as jnp
        ex = self.server.executor
        S = self.mix["prompt_tokens"]
        top = self.max_batch()
        firsts = {}                 # first layer of each (segment, element)
        for i, (_, seg, elem, _) in enumerate(ex.layers):
            firsts.setdefault((seg, elem), i)
        for B in range(1, top + 1):
            h = ex.embed(jnp.zeros((B, S), jnp.int32))
            for k in range(1, B + 1):
                mask = np.zeros(B, bool)
                mask[:k] = True
                h[mask].block_until_ready()
            for i in firsts.values():
                h = ex.run_blocks(h, i, i + 1)
            jax.block_until_ready(ex.head(h))
        super().warm()

    def answers(self) -> dict:
        out = super().answers()
        out["kept"] = {i: o[r].copy() for i, o, r in self.kept}
        out["tokens"] = {i: self.tokens(i) for i in out["kept"]}
        return out

    def close(self) -> None:
        self.server = None
        super().close()


class WavesDrive:
    """The grouping planner on a ``waves`` mix: one ``plan_fleet`` call per
    wave of users that arrive together."""

    served = False

    def __init__(self, config: dict, mix: dict, seed: int, root=None):
        from repro.core import PlannerService
        self.config, self.mix, self.seed = config, mix, seed
        self.P = deploy.task_profile(config, root)
        self.E = deploy.edge_profile(self.P, config["edge"])
        self.sort_keys = tuple(config["planner"]["waves"])
        self.profile, self.edge = program_profile(self.P), program_edge(self.E)
        self.service = PlannerService(self.profile, self.edge,
                                      rho=config["planner"]["rho"])
        self.inner = _inner(self.sort_keys)
        self.trace = False
        self.probe = None
        self.results: list = []
        self.wave = 0

    def wave_fleet(self, w: int) -> dict:
        return deploy.fleet(self.P, self.E, self.config["fleet"],
                            traffic.wave_betas(self.mix, self.seed, w))

    def _plan(self, fl: dict):
        return self.service.plan_fleet(program_fleet(fl), self.inner)

    def warm(self, waves: int = 2) -> None:
        """Plan waves of another seed: every level shape of this wave size."""
        for w in range(waves):
            self._plan(deploy.fleet(self.P, self.E, self.config["fleet"],
                                    traffic.wave_betas(self.mix, self.seed,
                                                       WARM_WAVE + w)))

    def start(self, trace: bool) -> None:
        self.trace = trace
        self.probe = PlannerProbe(self.service)

    def flush(self) -> Flush:
        with annotate(self.trace, "bench.submit"):
            fl = self.wave_fleet(self.wave)
            pf = program_fleet(fl)
        t0 = time.perf_counter()
        with annotate(self.trace, "bench.plan"):
            g = self.service.plan_fleet(pf, self.inner)
        ms = (time.perf_counter() - t0) * 1e3
        self.results.append(g)
        self.wave += 1
        return Flush(ms, len(fl["deadline"]), 0, *self.probe.delta())

    def answers(self) -> dict:
        return dict(waves=[dict(energy=g.energy,
                                groups=[np.asarray(x).tolist()
                                        for x in g.groups],
                                plans=[dict(partition=s.partition,
                                            offload=np.asarray(s.offload),
                                            f_device=np.asarray(s.f_device),
                                            f_edge=s.f_edge,
                                            energy=s.energy)
                                       for s in g.schedules])
                           for g in self.results])

    def close(self) -> None:
        self.service.close()


def drive(config: dict, mix: dict, seed: int, root=None):
    """The cell's drive; ``root`` is where its configuration's files lie
    (the model's reference module among them)."""
    if "model" in config:
        return ServedDrive(config, mix, seed, root)
    if mix["mode"] == "online":
        return OnlineDrive(config, mix, seed, root)
    if mix["mode"] == "waves":
        return WavesDrive(config, mix, seed, root)
    raise ValueError(f"no drive for mix mode {mix['mode']!r}")
