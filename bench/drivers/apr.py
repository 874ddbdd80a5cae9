"""CP-APR through `cpapr.cp_apr`: one step is one outer iteration, every
mode updated by the traffic's `inner_iterations` Φ steps.

Set-up runs `CHECK_STEPS` outer iterations from the benchmark's seeded
(λ, factors) through the same call the window makes: two, so the
programs of the first outer iteration and of the later ones are both
compiled before the window. The window continues from their state. The
reference follows them from the same state, and their answer is judged by
its gap to the reference's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHECK_STEPS = 2


def initial(key, dims, rank: int, total: float):
    """Factors uniform on [0.1, 1.1) with unit column sums; λ = total/R,
    so the model starts with the tensor's mass."""
    factors = []
    for k, I in zip(jax.random.split(key, len(dims)), dims):
        A = jax.random.uniform(k, (I, rank), jnp.float32, 0.1, 1.1)
        factors.append(A / jnp.sum(A, axis=0, keepdims=True))
    return jnp.full((rank,), total / rank, jnp.float32), factors


def _params(ctx, steps: int):
    from repro.core import cpapr
    return cpapr.CpaprParams(k_max=steps, tau=ctx.traffic["tolerance"],
                             l_max=ctx.traffic["inner_iterations"])


def solve(ctx, state, steps: int):
    from repro.core import cpapr
    return cpapr.cp_apr(ctx.at, ctx.rank, params=_params(ctx, steps),
                        warm_start=state, views=ctx.views, plan=ctx.plan)


def next_state(result):
    return result.lam, result.factors


def steps_run(result) -> int:
    return result.n_outer


def outputs(result):
    return result.lam, result.factors


def reference(ref, coo, state, config: dict, traffic: dict,
              control: bool = False):
    """The reference's (λ, factors) after `CHECK_STEPS` outer iterations
    from ``state``, which a warm start first clamps above 1e-10 and
    renormalizes to unit column sums. The control computes in bfloat16,
    one step below the configuration's float32."""
    if config["dtype"] != "float32":
        raise ValueError(f"no control defined below {config['dtype']!r}")
    lam, factors = state
    factors = [jnp.maximum(A, 1e-10) for A in factors]
    factors = [A / jnp.sum(A, axis=0, keepdims=True) for A in factors]
    dtype = jnp.bfloat16 if control else jnp.float32
    return ref.apr_outer(coo.coords, coo.values, lam, factors,
                         outer=CHECK_STEPS, inner=traffic["inner_iterations"],
                         dtype=dtype)


def gaps(ref, coo, state, got, config: dict, traffic: dict) -> dict:
    """The numbers `correct` compares for ``got``, the (λ, factors) after
    `CHECK_STEPS` outer iterations from ``state``."""
    want = reference(ref, coo, state, config, traffic)
    return {"state_gap": ref.state_gap(*got, *want)}
