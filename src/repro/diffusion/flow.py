"""Rectified flow / flow matching — the paper notes FlexiDiT "is largely
agnostic to the diffusion process and can be applied out of the box for
flow matching methods" (App. A). This module makes that concrete: linear
interpolation path x_t = (1−τ)·x0 + τ·ε, velocity target v = ε − x0,
Euler/Heun integrators with the same *phased* structure as the DDPM
samplers, so the weak→powerful FlexiSchedule drops straight in.

τ convention: τ ∈ [0,1], τ=1 is pure noise (matches the diffusion-t
direction so schedulers transfer unchanged; model conditioning uses
τ·1000 to reuse the timestep embedding range).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# v_fn(x, tau[B]) -> velocity prediction (= eps - x0 target)
VFn = Callable[[jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class FlowSchedule:
    """Discrete rectified-flow schedule of a model trained on
    ``num_steps`` timesteps, conditioned on σ·``num_steps``, with SD3's
    resolution shift σ' = shift·σ / (1 + (shift − 1)·σ) (diffusers'
    ``FlowMatchEulerDiscreteScheduler``)."""
    num_steps: int = 1000
    shift: float = 1.0

    def shifted(self, sigma):
        return self.shift * sigma / (1.0 + (self.shift - 1.0) * sigma)

    def sigmas(self, T: int) -> np.ndarray:
        """The T descending σ an Euler sampler visits, as the diffusers
        scheduler's ``set_timesteps`` gives them: evenly spaced between
        the shifted σ of the first and of the last training timestep,
        then shifted. The step after the last one lands on σ = 0."""
        hi, lo = self.shifted(1.0), self.shifted(1.0 / self.num_steps)
        return self.shifted(np.linspace(hi, lo, T))


def euler_step(x: jax.Array, v: jax.Array, sigma: jax.Array,
               sigma_next: jax.Array) -> jax.Array:
    """x at ``sigma_next`` from x at ``sigma`` and the velocity v (one
    σ per batch row)."""
    ds = (sigma_next - sigma).reshape((-1,) + (1,) * (x.ndim - 1))
    return x + ds * v


def interpolate(x0: jax.Array, eps: jax.Array, tau: jax.Array) -> jax.Array:
    tau = tau.reshape((-1,) + (1,) * (x0.ndim - 1))
    return (1.0 - tau) * x0 + tau * eps


def velocity_target(x0: jax.Array, eps: jax.Array) -> jax.Array:
    return eps - x0


def flow_matching_loss(v_pred: jax.Array, x0: jax.Array,
                       eps: jax.Array) -> jax.Array:
    v = velocity_target(x0, eps)
    return jnp.mean(jnp.square(v_pred.astype(jnp.float32)
                               - v.astype(jnp.float32)))


def tau_ladder(num_steps: int) -> np.ndarray:
    """Descending τ ladder 1 → 0 (sampling order), num_steps intervals."""
    return np.linspace(1.0, 0.0, num_steps + 1)


def euler_phase(v_fn: VFn, x: jax.Array, taus: np.ndarray) -> jax.Array:
    """Integrate dx/dτ = v from taus[0] down to taus[-1] (Euler)."""
    t_hi = jnp.asarray(taus[:-1], jnp.float32)
    t_lo = jnp.asarray(taus[1:], jnp.float32)

    def body(x, inp):
        ta, tb = inp
        tau_b = jnp.full((x.shape[0],), ta, jnp.float32)
        v = v_fn(x, tau_b)
        return x + (tb - ta) * v, None

    x, _ = jax.lax.scan(body, x, (t_hi, t_lo))
    return x


def heun_phase(v_fn: VFn, x: jax.Array, taus: np.ndarray) -> jax.Array:
    """2nd-order Heun integrator (2 NFEs per step)."""
    t_hi = jnp.asarray(taus[:-1], jnp.float32)
    t_lo = jnp.asarray(taus[1:], jnp.float32)

    def body(x, inp):
        ta, tb = inp
        dt = tb - ta
        tau_a = jnp.full((x.shape[0],), ta, jnp.float32)
        tau_b = jnp.full((x.shape[0],), tb, jnp.float32)
        v1 = v_fn(x, tau_a)
        x_pred = x + dt * v1
        v2 = v_fn(x_pred, tau_b)
        return x + dt * 0.5 * (v1 + v2), None

    x, _ = jax.lax.scan(body, x, (t_hi, t_lo))
    return x


def sample_flow_phased(phases: Sequence[Tuple[VFn, np.ndarray]],
                       x_T: jax.Array, solver: str = "euler") -> jax.Array:
    """Chain phases exactly like diffusion.sampler.sample_phased: each phase
    is (v_fn, its τ SUB-LADDER incl. its end point). The FlexiSchedule's
    weak→powerful split applies unchanged."""
    fn = euler_phase if solver == "euler" else heun_phase
    x = x_T
    for v_fn, taus in phases:
        if len(taus) >= 2:
            x = fn(v_fn, x, taus)
    return x


def split_tau_ladder(taus: np.ndarray, phases: Sequence[Tuple[int, int]]
                     ) -> List[Tuple[int, np.ndarray]]:
    """Split a τ ladder across (mode, n_steps) phases, duplicating boundary
    points so each phase integrates a contiguous interval."""
    out, i = [], 0
    for mode, n in phases:
        out.append((mode, taus[i:i + n + 1]))
        i += n
    return out


def make_flow_v_fn(params, cfg, cond, mode: int = 0, parallel=None,
                   attn_backend: str = "auto") -> VFn:
    """Wrap a (learn_sigma=False) DiT as a velocity model: the τ∈[0,1] time
    is mapped onto the timestep-embedding range. ``parallel`` threads the
    sequence-parallel engine into the NFE (repro.distributed)."""
    from repro.models import dit as dit_mod

    def v_fn(x, tau):
        out = dit_mod.dit_forward(params, x, tau * 1000.0, cond, cfg,
                                  mode=mode, parallel=parallel,
                                  attn_backend=attn_backend)
        return dit_mod.eps_prediction(out, cfg)

    return v_fn
