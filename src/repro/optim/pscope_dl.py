"""pSCOPE for deep models — the paper's CALL schedule as a distributed
train step for any model in the zoo.

Composite objective:  L(w) = loss(w) + (lam1/2)||w||^2 + lam2 ||w||_1
(sparse training / pruning-aware finetuning).

One outer step (shard_map, MANUAL over the worker axes, AUTO over the
remaining mesh axes so FSDP/TP collectives stay XLA-managed):

  phase 1   z = pmean_workers( mean_mb grad loss(w_t) )   [1 all-reduce,
            optionally top-k compressed with error feedback]
  phase 2   M inner steps, NO worker-axis collectives:
              u <- prox_{R,eta}( u - eta (g(u;mb) - g(w_t;mb) + z) )
  phase 3   w_{t+1} = pmean_workers(u)                    [1 all-reduce]

Worker axes:
  * multi-pod mesh: workers = ("pod",) — a pSCOPE worker is one pod;
    the inner loop contains only intra-pod (fast ICI) collectives and
    the two cross-pod (slow DCI) all-reduces per outer step are the
    whole inter-pod traffic.  This is the paper's cluster hierarchy
    mapped onto TPU fabric.
  * single-pod mesh: workers = ("data",) with TP-replicated params.

The standard baseline step (grad-accumulate + AdamW, per-step DP
all-reduce) is `make_standard_train_step` — the communication-cost
comparison in EXPERIMENTS.md §Roofline is pSCOPE's Table-1 claim at
datacenter scale.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.core.prox import Regularizer
from repro.optim import optimizers as opt

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PScopeDLConfig:
    eta: float = 1e-2              # inner learning rate
    inner_steps: int = 4           # M
    num_microbatches: int = 4      # microbatch split of the local batch
    lam1: float = 0.0
    lam2: float = 0.0
    worker_axes: Tuple[str, ...] = ("pod",)
    z_dtype: Any = jnp.float32
    compression_ratio: float = 0.0   # 0 = off; else keep-fraction for z
    grad_clip: float = 0.0
    # Unrolling the (small) z/inner loops trades HLO size for giving
    # XLA freedom to specialize each microbatch step; scan keeps compile
    # time down for 90+-layer models.  (The microbatch SPLIT must happen
    # outside the manual region either way — see make_pscope_train_step.)
    unroll_loops: bool = False


def init_train_state(params, cfg: PScopeDLConfig) -> Dict[str, Any]:
    """pSCOPE needs no Adam moments — state is the error-feedback
    residual (only if compression is on) plus the step counter."""
    state: Dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
    if cfg.compression_ratio > 0:
        state["ef"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, cfg.z_dtype), params)
    return state


def _split_mb(batch: Dict[str, Array], n_mb: int) -> Dict[str, Array]:
    def sp(x):
        b = x.shape[0]
        return x.reshape(n_mb, b // n_mb, *x.shape[1:])

    return {k: sp(v) for k, v in batch.items()}


def _take_mb(mbs: Dict[str, Array], i) -> Dict[str, Array]:
    return {k: jax.lax.dynamic_index_in_dim(v, i, axis=0, keepdims=False)
            for k, v in mbs.items()}


def _topk_mask(x: Array, keep_frac: float) -> Array:
    k = max(1, int(x.size * keep_frac))
    flat = jnp.abs(x.reshape(-1))
    thresh = jax.lax.top_k(flat, k)[0][-1]
    return (jnp.abs(x) >= thresh).astype(x.dtype)


def _strip_axes(rules: Dict, removed: Tuple[str, ...]) -> Dict:
    """Remove mesh axes from logical rules (for code running inside a
    shard_map that is manual over `removed` — sharding constraints may
    only reference the remaining auto axes)."""
    out = {}
    for k, v in rules.items():
        if isinstance(v, (tuple, list)):
            kept = tuple(a for a in v if a not in removed)
            out[k] = kept if kept else None
        elif v in removed:
            out[k] = None
        else:
            out[k] = v
    return out


def make_pscope_train_step(model, mesh, cfg: PScopeDLConfig,
                           donate: bool = True) -> Callable:
    """Returns jit'd (state, params, batch) -> (params, state, metrics)."""
    from repro.models import build_model

    reg = Regularizer(cfg.lam1, cfg.lam2)
    waxes = tuple(a for a in cfg.worker_axes if a in mesh.axis_names)
    # the body runs with `waxes` manual: rebind the model to rules that
    # only reference the remaining (auto) axes, or every activation
    # constraint inside would be invalid and XLA would lose the
    # intended sharding (=> replicated compute over `model`).
    inner_rules = _strip_axes(model.rules, waxes)
    inner_rules["_xent_onehot"] = True   # gather-free CE under manual mesh
    # sequence-sharded activation constraints (SP residual stream, SP
    # attention fallback, MoE capacity) trip this XLA's partitioner
    # inside manual submeshes ("invalid binary instruction opcode copy" /
    # CHECK spmd_partitioner_util.cc:504); they are memory optimizations
    # for the big-model path, which uses the stacked formulation instead
    inner_rules["res_seq"] = None
    inner_rules["attn_seq"] = None
    inner_rules["moe_cap"] = None
    inner_model = build_model(model.cfg, inner_rules)

    def loss_fn(params, mb):
        return inner_model.loss(params, mb)

    def body(params, state, mbs, key):
        # mbs: pre-split {name: (n_mb, b_local, ...)} — the microbatch
        # reshape happens OUTSIDE the manual region (resharding a
        # worker-sharded dim inside it trips the SPMD partitioner).
        n_mb = cfg.num_microbatches
        w_t = params

        # ---- phase 1: anchor (full) gradient, one worker all-reduce ----
        def z_acc(carry, i):
            z = carry
            g = jax.grad(loss_fn)(w_t, _take_mb(mbs, i))
            z = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(cfg.z_dtype) / n_mb, z, g)
            return z, None

        z0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, cfg.z_dtype), w_t)
        if cfg.unroll_loops:
            z_local = z0
            for i in range(n_mb):
                z_local, _ = z_acc(z_local, i)
        else:
            z_local, _ = jax.lax.scan(z_acc, z0, jnp.arange(n_mb))

        if cfg.compression_ratio > 0:
            # top-k sparsification with error feedback: send only the
            # largest entries; the residual stays local and is added to
            # the next round's gradient (Stich et al. style).
            def comp(zl, ef):
                acc = zl + ef
                mask = _topk_mask(acc, cfg.compression_ratio)
                sent = acc * mask
                return sent, acc - sent

            comp_out = jax.tree_util.tree_map(comp, z_local, state["ef"])
            z_local = jax.tree_util.tree_map(
                lambda o: o[0], comp_out,
                is_leaf=lambda x: isinstance(x, tuple))
            new_ef = jax.tree_util.tree_map(
                lambda o: o[1], comp_out,
                is_leaf=lambda x: isinstance(x, tuple))
        else:
            new_ef = None

        z = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, waxes), z_local)

        # ---- phase 2: M local inner steps, zero worker collectives -----
        def inner(u, m):
            mb = _take_mb(mbs, m % n_mb)
            g_u = jax.grad(loss_fn)(u, mb)
            g_w = jax.grad(loss_fn)(w_t, mb)

            def upd(uu, gu, gw, zz):
                v = (gu.astype(jnp.float32) - gw.astype(jnp.float32)
                     + zz.astype(jnp.float32))
                t = uu.astype(jnp.float32) - cfg.eta * v
                # elastic-net prox
                st = jnp.sign(t) * jnp.maximum(
                    jnp.abs(t) - cfg.eta * cfg.lam2, 0.0)
                return (st / (1.0 + cfg.eta * cfg.lam1)).astype(uu.dtype)

            return jax.tree_util.tree_map(upd, u, g_u, g_w, z), None

        if cfg.unroll_loops:
            u = w_t
            for m in range(cfg.inner_steps):
                u, _ = inner(u, m)
        else:
            u, _ = jax.lax.scan(inner, w_t, jnp.arange(cfg.inner_steps))

        # ---- phase 3: cooperative averaging, one worker all-reduce -----
        u = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a.astype(jnp.float32),
                                    waxes).astype(a.dtype), u)

        loss0 = loss_fn(w_t, _take_mb(mbs, 0))
        loss0 = jax.lax.pmean(loss0, waxes)
        metrics = {"loss": loss0, "z_norm": opt.global_norm(z)}
        new_state = {"step": state["step"] + 1}
        if new_ef is not None:
            new_state["ef"] = new_ef
        return u, new_state, metrics

    # shard_map: manual over worker axes only; model/fsdp axes stay auto
    in_specs = (P(), P(), P(None, waxes), P())
    out_specs = (P(), P(), P())
    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=in_specs, out_specs=out_specs,
                            axis_names=set(waxes), check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(params, state, batch, key):
        mbs = _split_mb(batch, cfg.num_microbatches)
        return sharded(params, state, mbs, key)

    return train_step


def make_pscope_train_step_stacked(model, mesh, cfg: PScopeDLConfig,
                                   donate: bool = True) -> Callable:
    """pSCOPE step with the worker axis as a STACKED ARRAY DIM instead
    of a manual shard_map submesh.

    The local iterates u (and per-worker microbatches) carry a leading
    dim of size W = prod(worker_axes), constrained to shard over the
    worker axes; all per-worker computation is `vmap`ed over it.  XLA
    then partitions worker w's compute onto worker w's devices with NO
    cross-worker collectives (the vmap dim is embarrassingly parallel),
    and the two phase reductions are plain `mean(axis=0)` — lowered to
    exactly one cross-worker all-reduce each.

    This formulation composes with FSDP param sharding (the manual
    shard_map variant trips XLA's SPMD partitioner when `data` is both
    an FSDP axis and auto inside a manual submesh).  Semantically
    identical to `make_pscope_train_step`.
    """
    from repro.models import build_model

    waxes = tuple(a for a in cfg.worker_axes if a in mesh.axis_names)
    W = 1
    for a in waxes:
        W *= mesh.shape[a]
    inner_rules = _strip_axes(model.rules, waxes)
    inner_model = build_model(model.cfg, inner_rules)
    n_mb = cfg.num_microbatches
    # stacked pspecs: worker axes on dim0, the PARAM sharding (FSDP/TP)
    # preserved on the remaining dims — a bare P(waxes) constraint would
    # force the param dims replicated and blow up per-chip memory
    param_pspecs = inner_model.param_pspecs()
    stacked_pspecs = jax.tree_util.tree_map(
        lambda s: P(waxes, *tuple(s)), param_pspecs)
    batch_rest = inner_rules.get("batch")

    def loss_fn(params, mb):
        return inner_model.loss(params, mb)

    def worker_split(batch):
        """{k: (B, ...)} -> {k: (W, n_mb, B/(W*n_mb), ...)} with dim0
        sharded over the worker axes, dim2 over the remaining DP axes."""
        out = {}
        for k, v in batch.items():
            b = v.shape[0]
            vv = v.reshape(W, n_mb, b // (W * n_mb), *v.shape[1:])
            out[k] = jax.lax.with_sharding_constraint(
                vv, P(waxes, None, batch_rest))
        return out

    def shard_stack(tree):
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(a, s), tree,
            stacked_pspecs)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(params, state, batch, key):
        wmbs = worker_split(batch)
        w_t = params

        # ---- phase 1: per-worker anchor grad, then ONE all-reduce ----
        def z_worker(mb_stack):
            def acc(z, i):
                g = jax.grad(loss_fn)(w_t, _take_mb(mb_stack, i))
                return jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(cfg.z_dtype) / n_mb, z, g), None

            z0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, cfg.z_dtype), w_t)
            if cfg.unroll_loops:
                z = z0
                for i in range(n_mb):
                    z, _ = acc(z, i)
                return z
            z, _ = jax.lax.scan(acc, z0, jnp.arange(n_mb))
            return z

        z_stack = shard_stack(jax.vmap(z_worker)(wmbs))      # (W, ...)
        z = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), z_stack)

        # ---- phase 2: per-worker local inner steps (no collectives) ---
        def inner_worker(mb_stack):
            def inner(u, m):
                mb = _take_mb(mb_stack, m % n_mb)
                g_u = jax.grad(loss_fn)(u, mb)
                g_w = jax.grad(loss_fn)(w_t, mb)

                def upd(uu, gu, gw, zz):
                    v = (gu.astype(jnp.float32) - gw.astype(jnp.float32)
                         + zz.astype(jnp.float32))
                    t = uu.astype(jnp.float32) - cfg.eta * v
                    st = jnp.sign(t) * jnp.maximum(
                        jnp.abs(t) - cfg.eta * cfg.lam2, 0.0)
                    return (st / (1.0 + cfg.eta * cfg.lam1)).astype(uu.dtype)

                return jax.tree_util.tree_map(upd, u, g_u, g_w, z), None

            if cfg.unroll_loops:
                u = w_t
                for m in range(cfg.inner_steps):
                    u, _ = inner(u, m)
                return u
            u, _ = jax.lax.scan(inner, w_t, jnp.arange(cfg.inner_steps))
            return u

        u_stack = shard_stack(jax.vmap(inner_worker)(wmbs))  # (W, ...)

        # ---- phase 3: cooperative averaging, ONE all-reduce -----------
        new_params = jax.tree_util.tree_map(
            lambda a, p: jnp.mean(a.astype(jnp.float32),
                                  axis=0).astype(p.dtype), u_stack, params)

        loss0 = loss_fn(w_t, _take_mb({k: v[0] for k, v in wmbs.items()}, 0))
        metrics = {"loss": loss0, "z_norm": opt.global_norm(z)}
        return new_params, {"step": state["step"] + 1}, metrics

    return train_step


# ---------------------------------------------------------------------------
# standard baseline: grad-accumulation + AdamW, per-step DP all-reduce
# ---------------------------------------------------------------------------

def make_standard_train_step(model, mesh, num_microbatches: int = 4,
                             lr: float = 1e-4, weight_decay: float = 0.01,
                             moment_dtype=jnp.float32,
                             donate: bool = True) -> Callable:
    """Fully auto-sharded (GSPMD) reference step: scan over microbatches
    accumulating the mean gradient, then one AdamW update.  Under DP the
    gradient mean over the batch axes makes XLA insert the classic
    per-step all-reduce; under FSDP the per-layer all-gather /
    reduce-scatter pattern.  This is the communication baseline that
    pSCOPE's CALL schedule amortizes."""

    def loss_fn(params, mb):
        return model.loss(params, mb)

    @functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def train_step(params, opt_state, batch, key):
        mbs = _split_mb(batch, num_microbatches)

        def acc(carry, i):
            g_acc, l_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, _take_mb(mbs, i))
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32) / num_microbatches,
                g_acc, g)
            return (g_acc, l_acc + l / num_microbatches), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g, loss), _ = jax.lax.scan(acc, (g0, 0.0),
                                    jnp.arange(num_microbatches))
        new_params, new_opt = opt.adamw_update(g, opt_state, params, lr,
                                               weight_decay=weight_decay)
        return new_params, new_opt, {"loss": loss,
                                     "grad_norm": opt.global_norm(g)}

    return train_step
