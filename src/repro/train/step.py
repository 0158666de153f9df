"""Train-step builders.

``build_train_step``      — GSPMD path: grads/optimizer collectives inserted
                            by the compiler from the param shardings
                            (FSDP x TP); microbatch accumulation via scan.
``build_train_step_compressed_dp`` — explicit-DP path: shard_map over the
                            data-parallel axes ("pod","data") with the model
                            axis left automatic; the gradient all-reduce is
                            the MX-compressed exchange from
                            repro.core.grad_compress (ZeRO-1 posture:
                            params replicated over DP, optimizer sharded by
                            the launcher).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.grad_compress import mx_allreduce_tree
from repro.models.registry import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab: int) -> jax.Array:
    """Mean CE over valid positions (labels in [0, vocab); -1 = masked).
    Computed in f32; padded-vocab columns are never valid labels."""
    logits = logits.astype(jnp.float32)
    valid = (labels >= 0) & (labels < vocab)
    labs = jnp.clip(labels, 0, vocab - 1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labs[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * valid
    return jnp.sum(ce) / jnp.maximum(jnp.sum(valid), 1)


def _loss_fn(model: Model, params, batch, *, fake_quant: bool,
             aux_weight: float = 0.01):
    logits, aux = model.forward(params, batch, fake_quant=fake_quant)
    labels = batch["labels"]
    # align: forward emits one logit per input position; labels are
    # already next-token-shifted by the pipeline
    s = min(logits.shape[1], labels.shape[1])
    ce = cross_entropy(logits[:, :s], labels[:, :s], model.cfg.vocab)
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def build_train_step(model: Model, opt_cfg: AdamWConfig, *,
                     microbatches: int = 1, fake_quant: bool = False,
                     donate: bool = True) -> Callable:
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics).  Not jitted — the launcher jits with
    shardings."""
    cfg = model.cfg
    param_dtype = jnp.dtype(cfg.param_dtype)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: _loss_fn(model, p, batch, fake_quant=fake_quant),
            has_aux=True)(params)

    def train_step(params, opt_state, batch, step):
        if microbatches == 1:
            (loss, met), grads = grads_of(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def acc_step(carry, mbatch):
                g_acc, l_acc = carry
                (loss, met), g = grads_of(params, mbatch)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b2: a + b2.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), met

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), mets = jax.lax.scan(acc_step, (g0, 0.0), mb)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches,
                                           grads)
            loss = loss / microbatches
            met = jax.tree_util.tree_map(lambda m: m[-1], mets)
        new_params, new_opt, omet = adamw_update(
            opt_cfg, grads, opt_state, step, param_dtype)
        metrics = {"loss": loss, **met, **omet}
        return new_params, new_opt, metrics

    return train_step


def build_train_step_compressed_dp(model: Model, opt_cfg: AdamWConfig, *,
                                   mesh, dp_axes: Sequence[str],
                                   spec=None, fmt: Optional[str] = None,
                                   mode: Optional[str] = None,
                                   fake_quant: bool = False) -> Callable:
    """Explicit-DP train step: per-shard grads + MX-compressed all-reduce.

    Parameters are replicated over the DP axes (ZeRO-1); any "model" axis
    stays automatic (GSPMD handles TP inside the shard_map body).

    The gradient-exchange ``spec`` defaults to the model policy's
    ``grads`` role (else e4m3/ocp); the ``fmt=``/``mode=`` kwargs are the
    deprecation shim.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.spec import QuantSpec, resolve_spec

    cfg = model.cfg
    if spec is None and fmt is None and mode is None:
        spec = cfg.mx.grads or QuantSpec("e4m3", "ocp")
    else:
        spec = resolve_spec(spec, fmt, mode, None,
                            default=QuantSpec("e4m3", "ocp"),
                            caller="build_train_step_compressed_dp")
    param_dtype = jnp.dtype(cfg.param_dtype)
    dp = tuple(dp_axes)

    batch_spec = P(dp)      # batch dim sharded over DP axes
    rep = P()

    def body(params, opt_state, batch, step):
        (loss, met), grads = jax.value_and_grad(
            lambda p: _loss_fn(model, p, batch, fake_quant=fake_quant),
            has_aux=True)(params)
        grads = mx_allreduce_tree(grads, dp, spec)
        loss = jax.lax.pmean(loss, dp)
        new_params, new_opt, omet = adamw_update(
            opt_cfg, grads, opt_state, step, param_dtype)
        return new_params, new_opt, {"loss": loss, **met, **omet}

    def specs_like(tree, spec):
        return jax.tree_util.tree_map(lambda _: spec, tree)

    def train_step(params, opt_state, batch, step):
        in_specs = (specs_like(params, rep), specs_like(opt_state, rep),
                    specs_like(batch, batch_spec), rep)
        out_specs = (specs_like(params, rep), specs_like(opt_state, rep),
                     {"loss": rep, "ce": rep, "aux": rep, "grad_norm": rep,
                      "lr": rep})
        # manual over the DP axes only; any "model" axis stays automatic
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False,
                           axis_names=set(dp))
        return fn(params, opt_state, batch, step)

    return train_step


def init_train_state(model: Model, key) -> Tuple[Any, Any]:
    params = model.init(key)
    return params, adamw_init(params)
