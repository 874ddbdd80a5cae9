"""GPipe pipeline parallelism over the model stack (dist seam #2).

The depth dimension of `models.model` is a stack of `n_repeats` block
groups; pipeline parallelism cuts that stack into ``n_stages`` contiguous
stages, one per device on the mesh's first axis, and streams microbatches
through them:

* `to_pipeline_params` reshapes each ``blocks_<pos>`` parameter stack
  from ``(n_repeats, ...)`` to ``(n_stages, n_repeats // n_stages, ...)``
  — the leading axis is what `shard_map` shards, so every device holds
  only its stage's layers;
* `pipeline_forward` runs the classic GPipe schedule inside one
  `shard_map`: for ``n_microbatches + n_stages − 1`` ticks, every device
  applies its stage to its current microbatch activation, then the
  activations rotate one stage forward with ``ppermute``. Stage 0 injects
  microbatch ``t`` at tick ``t``; the last stage emits microbatch
  ``t − (n_stages − 1)``. Bubble-tick outputs are computed on zeros and
  masked out (gather via ``where`` + final ``psum``), so they contribute
  nothing to values or gradients;
* `pipeline_loss` is the training entry: same schedule under
  ``jax.grad``. ``ppermute`` transposes to the inverse rotation, so
  backward runs the symmetric reverse schedule automatically — no hand
  written backward pipeline.

Equivalence invariant: stage ``s`` applies repeats ``[s·per, (s+1)·per)``
in the same inner order as `model.forward_hidden`'s scan (pattern position
inner, repeat outer), and embedding / final norm / unembed stay replicated
outside the shard_map — so logits and gradients match the sequential
model to float roundoff (asserted by ``tests/test_pipeline.py``).

Scope: decoder-only families (dense/moe/ssm/hybrid). Encoder-decoder and
VLM prefixes keep their sequential path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.meshes import auto_axes
from repro.models import blocks as blk
from repro.models import model as model_lib
from repro.models.common import rmsnorm, unembed
from repro.train.steps import cross_entropy


def to_pipeline_params(cfg: ModelConfig, params, n_stages: int):
    """Regroup the depth stacks for ``n_stages`` pipeline stages.

    ``blocks_<pos>``: (n_repeats, ...) -> (n_stages, per_stage, ...),
    keeping repeat order — stage s owns the contiguous repeats
    [s*per, (s+1)*per). Embedding / norms / unembed pass through
    (replicated on every stage).
    """
    if cfg.n_repeats % n_stages:
        raise ValueError(f"n_repeats {cfg.n_repeats} not divisible by "
                         f"{n_stages} pipeline stages")
    per = cfg.n_repeats // n_stages
    out = {k: v for k, v in params.items() if not k.startswith("blocks_")}
    for pos in range(len(cfg.block_pattern)):
        out[f"blocks_{pos}"] = jax.tree.map(
            lambda a: a.reshape((n_stages, per) + a.shape[1:]),
            params[f"blocks_{pos}"])
    return out


def from_pipeline_params(cfg: ModelConfig, params):
    """Inverse of `to_pipeline_params` (merge stages back to one stack)."""
    out = {k: v for k, v in params.items() if not k.startswith("blocks_")}
    for pos in range(len(cfg.block_pattern)):
        out[f"blocks_{pos}"] = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]),
            params[f"blocks_{pos}"])
    return out


def _stage_apply(cfg: ModelConfig, blocks, x, positions, positions3):
    """Apply one stage's layer slice. ``blocks``: {pos: (1, per, ...)}
    (the local shard — leading stage axis is 1 inside shard_map)."""
    aux = jnp.zeros((), jnp.float32)
    per = jax.tree.leaves(blocks[0])[0].shape[1]
    for layer in range(per):
        for pos, btype in enumerate(cfg.block_pattern):
            p = jax.tree.map(lambda a: a[0, layer], blocks[pos])
            x, a = blk.block_apply(cfg, btype, p, x, positions=positions,
                                   positions3=positions3)
            aux = aux + a
    return x, aux


def _pipe_hidden(cfg: ModelConfig, blocks, x_stack, positions, positions3,
                 mesh, n_micro: int):
    """GPipe schedule under shard_map: (n_micro, mb, S, D) -> same + aux."""
    ax = mesh.axis_names[0]
    n_stages = int(mesh.shape[ax])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    step = functools.partial(_stage_apply, cfg)
    if cfg.remat:
        step = jax.checkpoint(step)

    def schedule(blocks, x_stack, positions, positions3):
        stage = jax.lax.axis_index(ax)
        state = jnp.zeros_like(x_stack[0])
        out = jnp.zeros_like(x_stack)
        aux = jnp.zeros((), jnp.float32)
        for t in range(n_micro + n_stages - 1):
            # stage 0 injects microbatch t (clamped reload in the drain
            # phase is bubble work, never collected).
            state = jnp.where(stage == 0, x_stack[min(t, n_micro - 1)],
                              state)
            state, a = step(blocks, state, positions, positions3)
            on_time = (t - stage >= 0) & (t - stage < n_micro)
            aux = aux + jnp.where(on_time, a, 0.0)
            m_out = t - (n_stages - 1)
            if m_out >= 0:      # last stage finished microbatch m_out
                out = out.at[m_out].set(
                    jnp.where(stage == n_stages - 1, state, out[m_out]))
            if t < n_micro + n_stages - 2:
                state = jax.lax.ppermute(state, ax, perm)
        # only the last stage holds real outputs; psum replicates them
        last = stage == n_stages - 1
        out = jax.lax.psum(jnp.where(last, out, jnp.zeros_like(out)), ax)
        aux = jax.lax.psum(aux, ax)
        return out, aux

    fn = jax.shard_map(schedule, mesh=auto_axes(mesh),
                       in_specs=(P(ax), P(), P(), P()), out_specs=(P(), P()))
    return fn(blocks, x_stack, positions, positions3)


def _forward_with_aux(cfg: ModelConfig, params, tokens, mesh,
                      n_microbatches: int):
    if cfg.is_encdec or cfg.family == "vlm":
        raise NotImplementedError(
            "pipeline parallelism covers decoder-only token models; "
            f"{cfg.name} ({cfg.family}) needs the sequential path "
            "(cross-attention / multimodal prefixes are not staged)")
    B, S = tokens.shape
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} "
                         "microbatches")
    x, positions, positions3 = model_lib._embed_inputs(
        cfg, params, {"tokens": tokens})
    mb = B // n_microbatches
    x_stack = x.reshape((n_microbatches, mb) + x.shape[1:])
    blocks = {pos: params[f"blocks_{pos}"]
              for pos in range(len(cfg.block_pattern))}
    hidden, aux = _pipe_hidden(cfg, blocks, x_stack, positions, positions3,
                               mesh, n_microbatches)
    hidden = hidden.reshape((B,) + hidden.shape[2:])
    hidden = rmsnorm(params["final_norm"], hidden, cfg.norm_eps)
    logits = unembed(model_lib.unembed_params(cfg, params), hidden)
    # per-microbatch aux losses are means over equal-size microbatches;
    # their average is the full-batch mean the sequential model reports
    return logits, aux / n_microbatches


def pipeline_forward(cfg: ModelConfig, params, tokens, mesh,
                     n_microbatches: int = 1) -> jnp.ndarray:
    """Pipelined forward: logits identical to `model.forward` (f32)."""
    logits, _ = _forward_with_aux(cfg, params, tokens, mesh, n_microbatches)
    return logits


def pipeline_loss(cfg: ModelConfig, params, batch, mesh,
                  n_microbatches: int = 1) -> jnp.ndarray:
    """Pipelined training loss (CE + router aux), `jax.grad`-able."""
    logits, aux = _forward_with_aux(cfg, params, batch["tokens"], mesh,
                                    n_microbatches)
    return cross_entropy(logits, batch["labels"]) + cfg.router_aux_coef * aux
