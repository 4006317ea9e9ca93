"""SALR fine-tuning train step and serving steps.

train_step: adapters-only gradients (frozen sparse base), microbatch
gradient accumulation (lax.scan), optional Theorem-4 residual LR scale,
optional int8 gradient compression before the optimizer (the compressed
all-reduce itself is exercised under shard_map in
repro.distributed.collectives).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import execplan
from repro.core.pytree import combine
from repro.core.salr import kernel_decode
from repro.models import model as M
from repro.optim.adamw import AdamW, residual_lr_scale_tree
from repro.train.state import TrainState


def _prefix_len(cfg: ArchConfig) -> int:
    return cfg.decode_prefix_len


def make_loss_fn(cfg: ArchConfig, loss_chunk: int = 512,
                 plan: Optional[execplan.ExecutionPlan] = None):
    prefix = _prefix_len(cfg)
    # Resolved once per step function; the train phase of the default
    # plan is the reference formulation (dense-decode GEMMs differentiate
    # natively, dense-masked MoE) — the serving steps below keep the
    # kernel routes of their own phases.
    plan = plan or execplan.resolve_plan(cfg)

    def loss_fn(trainable, frozen, batch):
        params = combine(trainable, frozen)
        x = M.forward_hidden(params, cfg, batch["tokens"],
                             batch.get("frontend"), plan=plan)
        # frontend prefix positions carry no labels
        with jax.named_scope("head"):
            return M.lm_loss_chunked(params["lm_head"], x, batch["labels"],
                                     prefix_len=prefix, chunk=loss_chunk)

    return loss_fn


def make_train_step(cfg: ArchConfig, opt: AdamW, *, microbatches: int = 1,
                    res_lr_scale: float = 1.0, loss_chunk: int = 512):
    """Returns train_step(state, batch) -> (state, metrics)."""
    loss_fn = make_loss_fn(cfg, loss_chunk=loss_chunk)

    def train_step(state: TrainState, batch):
        # the frozen tiled bases decode in the Pallas kernel on one TPU
        # chip (core/salr.kernel_decode); custom-VJP backwards trace
        # after the loss returns, so the scope holds the whole step
        with kernel_decode():
            return _step(state, batch)

    def _step(state: TrainState, batch):
        if microbatches > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])
            mbs = jax.tree_util.tree_map(split, batch)

            def accum(carry, mb):
                gsum, lsum = carry
                l, g = jax.value_and_grad(loss_fn)(state.trainable,
                                                   state.frozen, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.trainable)
            (gsum, lsum), _ = jax.lax.scan(accum, (g0, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.trainable,
                                                      state.frozen, batch)

        scales = residual_lr_scale_tree(state.trainable, res_lr_scale)
        new_tr, new_opt, om = opt.update(grads, state.opt, state.trainable,
                                         scales)
        metrics = {"loss": loss, **om}
        return TrainState(step=state.step + 1, trainable=new_tr,
                          frozen=state.frozen, opt=new_opt), metrics

    return train_step


# ------------------------------------------------------------- serving

def _serving_plan(cfg: ArchConfig,
                  plan: Optional[execplan.ExecutionPlan],
                  backend: Optional[str]) -> Optional[execplan.ExecutionPlan]:
    """Explicit plan wins; a bare ``backend`` string (compat spelling)
    resolves through the plan resolver; None defers to the model entry
    points (scope override, then the cfg-resolved default)."""
    if plan is not None:
        return plan
    if backend is not None:
        return execplan.resolve_plan(cfg, backend=backend)
    return None


def make_prefill_step(cfg: ArchConfig, backend: Optional[str] = None,
                      plan: Optional[execplan.ExecutionPlan] = None):
    """``plan`` pins the execution plan at trace time (the
    continuous-batching engine passes its resolved plan; ``backend`` is
    the compatibility spelling).  The optional ``logit_index`` batch
    entry reads the logits at the true last prompt token of a
    right-padded (bucketed) prompt.  The optional ``prefix_cache`` batch
    entry plus the ``pos_offset`` argument (STATIC int — jit callers
    must mark it static) run a continuation prefill over a shared-prefix
    cache (radix prefix sharing; see model.prefill)."""
    plan = _serving_plan(cfg, plan, backend)

    def prefill_step(params, batch, pos_offset: int = 0):
        return M.prefill(params, cfg, batch["tokens"],
                         batch.get("frontend"),
                         logit_index=batch.get("logit_index"), plan=plan,
                         prefix_cache=batch.get("prefix_cache"),
                         pos_offset=pos_offset)
    return prefill_step


def make_decode_step(cfg: ArchConfig, backend: Optional[str] = None,
                     plan: Optional[execplan.ExecutionPlan] = None):
    """``pos`` may be a scalar (uniform batch) or a (B,) vector of
    per-slot absolute positions (continuous batching)."""
    plan = _serving_plan(cfg, plan, backend)

    def decode_step(params, cache, tokens, pos):
        return M.decode_step(params, cfg, cache, tokens, pos, plan=plan)
    return decode_step


def prefill_for_decode(params, cfg: ArchConfig, prompt: jax.Array,
                       ctx: int, frontend: Optional[jax.Array] = None,
                       plan: Optional[execplan.ExecutionPlan] = None,
                       page_size: Optional[int] = None):
    """Prefill ``prompt`` and place its cache into a ``ctx``-wide decode
    cache in the plan's decode KV precision.  Returns (prefill logits,
    decode cache); the cache feeds ``M.decode_step`` at position
    ``prefix + prompt_len``.

    By default the cache is dense, so decode runs XLA attention (or the
    ring kernels for a quantized KV cache): an attention path
    independent of the serving engine's paged kernels, which is what the
    engine parity tests compare against.  ``page_size`` opts into a
    paged cache instead (row ``i`` owns pool pages ``1 + i * pages ...``
    of ``page_size`` positions, filled by the engine's slot insert), so
    decode runs the engine's paged-attention kernels; on a TPU those and
    XLA's attention differ in the last bits, which greedy argmax can
    amplify (chip_smoke.py)."""
    b, _ = prompt.shape
    logits, cache = M.prefill(params, cfg, prompt, frontend, plan=plan)
    resolved = plan or execplan.resolve_plan(cfg)
    kv_dtype = resolved.kv_dtype("decode")
    if page_size is not None:
        pages = -(-ctx // page_size)
        paged = M.init_paged_slot_cache(cfg, b, ctx, page_size=page_size,
                                        n_pages=b * pages + 1,
                                        kv_dtype=kv_dtype)
        paged["page_table"] = 1 + jnp.arange(
            b * pages, dtype=jnp.int32).reshape(b, pages)
        for i in range(b):
            paged = M.insert_paged_cache_slot(paged, _cache_row(cache, i),
                                              i, 0)
        return logits, paged
    skeleton = M.init_cache(cfg, b, ctx, kv_dtype=kv_dtype)

    def place(small, big):
        if small is None:
            return big
        if small.shape != big.shape:
            pads = [(0, bs - ss) for ss, bs in zip(small.shape, big.shape)]
            return jnp.pad(small, pads).astype(big.dtype)
        return small.astype(big.dtype)

    def place_obj(req_obj, slot_obj):
        # mixed-precision plans prefill native and quantize on the way
        # into the decode skeleton (same path the engine insert takes)
        return jax.tree_util.tree_map(
            place, M._quantize_request(slot_obj, req_obj), slot_obj)

    groups = [[{key: place_obj(rc[key], sc[key]) for key in sc}
               for rc, sc in zip(rgcs, sgcs)]
              for rgcs, sgcs in zip(cache["groups"], skeleton["groups"])]
    placed = dict(skeleton, groups=groups)
    if "memory" in skeleton:
        placed["memory"] = jax.tree_util.tree_map(
            place, cache["memory"], skeleton["memory"])
    return logits, placed


def _cache_row(cache, i: int):
    """Row ``i`` of a batched prefill cache as a batch-1 request cache
    (group leaves are (repeats, batch, ...), encoder memory (batch, ...))."""
    row = {"groups": jax.tree_util.tree_map(lambda a: a[:, i:i + 1],
                                            cache["groups"])}
    if "memory" in cache:
        row["memory"] = cache["memory"][i:i + 1]
    return row


def greedy_generate(params, cfg: ArchConfig, prompt: jax.Array,
                    n_steps: int, ctx: int,
                    frontend: Optional[jax.Array] = None,
                    plan: Optional[execplan.ExecutionPlan] = None,
                    page_size: Optional[int] = None) -> jax.Array:
    """Batched greedy decoding (examples / serving benchmark).
    ``plan`` pins per-phase routes — pass the SAME plan the engine under
    parity test uses, so both sides take identical routes.  Decode runs
    over a dense cache unless ``page_size`` opts into a paged one (see
    ``prefill_for_decode``)."""
    s = prompt.shape[1]
    prefix = _prefix_len(cfg)
    logits, cache = prefill_for_decode(params, cfg, prompt, ctx,
                                       frontend=frontend, plan=plan,
                                       page_size=page_size)
    tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    def body(carry, i):
        cache, tok = carry
        pos = prefix + s + i
        lg, cache = M.decode_step(params, cfg, cache, tok, pos, plan=plan)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return (cache, nxt), tok[:, 0]

    (_, _), toks = jax.lax.scan(body, (cache, tok0), jnp.arange(n_steps))
    return toks.T  # (B, n_steps)
