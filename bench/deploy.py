"""Deployment arithmetic: task profiles, the edge batch curve and the
Table-I device fleet, from a configuration file's numbers.

A copy of the program's own arithmetic (``repro.core.task_model`` and
``repro.core.cost_models``), kept here so that the yardstick does not move
when the program does: the benchmark computes these arrays once and hands
the same numbers to the system under test and to the references.  Units:
FLOPs, bytes, seconds, Hz, joules.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import model_module


@dataclasses.dataclass(frozen=True)
class Profile:
    """Per-sample block sequence, index 0 the virtual input layer."""

    name: str
    A: np.ndarray          # (N+1,) FLOPs per block
    O: np.ndarray          # (N+1,) boundary bytes
    g: np.ndarray          # (N+1,) device latency factor (Eq. 1)
    q: np.ndarray          # (N+1,) device energy factor (Eq. 2)

    @property
    def N(self) -> int:
        return len(self.A) - 1

    def v(self) -> np.ndarray:
        return np.cumsum(self.g * self.A)

    def u(self) -> np.ndarray:
        return np.cumsum(self.q * self.A)


@dataclasses.dataclass(frozen=True)
class Edge:
    """Affine batch profile of the edge accelerator (Eq. 5)."""

    f_min: float
    f_max: float
    delta0: np.ndarray
    delta1: np.ndarray
    eps0: np.ndarray
    eps1: np.ndarray

    @staticmethod
    def _suffix(x):
        return np.concatenate([np.cumsum(x[::-1])[::-1][1:], [0.0]])

    def phi_coeffs(self, p: Profile):
        """φ_ñ(B) = base[ñ] + slope[ñ]·B: GPU cycles of blocks > ñ."""
        return self._suffix(self.delta0 * p.A), self._suffix(self.delta1 * p.A)

    def psi_coeffs(self, p: Profile):
        """ψ_ñ(B) = base[ñ] + slope[ñ]·B: edge energy / f_e² of blocks > ñ."""
        return self._suffix(self.eps0 * p.A), self._suffix(self.eps1 * p.A)


def _bottleneck_macs(h, c_in, c_out, t, stride, reps):
    macs = 0.0
    for r in range(reps):
        s = stride if r == 0 else 1
        ci = c_in if r == 0 else c_out
        ho = h // s
        exp = t * ci
        if t != 1:
            macs += h * h * ci * exp                 # 1x1 expand
        macs += ho * ho * exp * 9                    # 3x3 depthwise
        macs += ho * ho * exp * c_out                # 1x1 project
        h = ho
    return macs, h


def mobilenet_v2_profile(input_res: int = 224, act_bytes: int = 4) -> Profile:
    """The paper's Fig. 2 partitioning of MobileNetV2(1.0): Conv, B1..B7,
    Conv, CLS (N = 10)."""
    stages = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
              (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
    h = input_res // 2
    A = [0.0, 2.0 * h * h * 32 * 27]
    O = [float(input_res * input_res * 3 * act_bytes),
         float(h * h * 32 * act_bytes)]
    c_in = 32
    for (t, c, n, s) in stages:
        macs, h = _bottleneck_macs(h, c_in, c, t, s, n)
        A.append(2.0 * macs)
        O.append(float(h * h * c * act_bytes))
        c_in = c
    A.append(2.0 * h * h * c_in * 1280)
    O.append(float(h * h * 1280 * act_bytes))
    A.append(2.0 * (1280 * 1000 + h * h * 1280))
    O.append(float(1000 * act_bytes))
    A, O = np.asarray(A), np.asarray(O)
    return Profile("mobilenet_v2", A, O, np.ones_like(A), np.ones_like(A))


def decoder_prefill_profile(model: dict, seq: int, act_bytes: int = 2,
                            root=None) -> Profile:
    """One block per decoder layer at prefill of ``seq`` tokens, each
    costing what the model's reference module states for that layer
    (``block_flops``); the embedding folds into block 1 and the LM head into
    block N (what ``profile_from_arch`` does)."""
    mod = model_module(model, root)
    L, d = model["num_layers"], model["d_model"]
    A = [0.0] + [mod.block_flops(model, i, seq) for i in range(L)]
    O = [float(seq * 4)] + [float(seq * d * act_bytes)] * L
    A[-1] += mod.head_flops(model, 1, seq)
    O[-1] = float(seq * model["vocab_size"] * act_bytes)
    A, O = np.asarray(A), np.asarray(O)
    return Profile(f"{model['arch']}:prefill@{seq}", A, O, np.ones_like(A),
                   np.ones_like(A))


def task_profile(config: dict, root=None) -> Profile:
    task = config["task"]
    if task["kind"] == "mobilenet_v2":
        return mobilenet_v2_profile(task["input_res"], task["act_bytes"])
    if task["kind"] == "dense_prefill":
        return decoder_prefill_profile(config["model"], task["seq"],
                                       task["act_bytes"], root)
    raise ValueError(f"unknown task kind {task['kind']!r}")


def edge_profile(p: Profile, e: dict) -> Edge:
    """Affine fit of Fig.-3-shaped batch curves (``make_edge_profile``)."""
    n = len(p.A)
    total = float(p.A.sum())
    d1 = e["lat_b1"] * e["f_max"] / (total * (e["batch_startup"] + 1.0))
    e1 = e["energy_b1"] / (total * e["f_max"] ** 2
                           * (e["energy_startup"] + 1.0))
    delta1, eps1 = np.full(n, d1), np.full(n, e1)
    return Edge(e["f_min"], e["f_max"], delta1 * e["batch_startup"], delta1,
                eps1 * e["energy_startup"], eps1)


def fleet(p: Profile, edge: Edge, f: dict, beta: np.ndarray) -> dict:
    """The Table-I fleet (``make_fleet``) for per-device deadline factors
    ``beta``: T_m = (1 + β_m) · local latency at f_max."""
    M = len(beta)
    rate = f["bandwidth_hz"] * np.log2(1.0 + 10 ** (f["snr_db"] / 10.0)) / 8.0
    phi_b, phi_s = edge.phi_coeffs(p)
    psi_b, psi_s = edge.psi_coeffs(p)
    lat_b1 = (phi_b[0] + phi_s[0]) / edge.f_max
    pow_b1 = (psi_b[0] + psi_s[0]) * edge.f_max ** 2 / lat_b1
    ones = np.ones(M)
    local_lat = f["alpha"] * lat_b1 * ones
    zeta = f["f_max"] * local_lat / p.v()[-1]
    local_pow = f["eta"] * pow_b1 * ones
    kappa = local_pow * local_lat / (p.u()[-1] * f["f_max"] ** 2)
    return dict(zeta=zeta, kappa=kappa, f_min=f["f_min"] * ones,
                f_max=f["f_max"] * ones, rate=rate * ones,
                p_up=f["p_up"] * ones,
                deadline=(1.0 + np.asarray(beta, np.float64)) * local_lat)


def f_sweep(edge: Edge, rho: float) -> np.ndarray:
    """Alg. 2's descending edge-frequency grid, f_max down to f_min."""
    k = int(np.floor((edge.f_max - edge.f_min) / rho + 1e-9)) + 1
    f = edge.f_max - rho * np.arange(k)
    if f[-1] - edge.f_min > 1e-9 * rho:
        f = np.concatenate([f, [edge.f_min]])
    else:
        f[-1] = edge.f_min
    return f


def subset(fl: dict, idx) -> dict:
    return {k: v[idx] for k, v in fl.items()}
