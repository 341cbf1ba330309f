"""int8 error-feedback gradient compression for the cross-pod reduce.

Multi-pod data parallelism pays one gradient all-reduce per step across
the DCN (25 GB/s vs 50 GB/s/link ICI).  Quantizing the cross-pod leg to
int8 cuts its wire bytes 4x vs f32 (2x vs bf16); the quantization residual
is carried forward per leaf and re-added next step (error feedback), which
keeps SGD/Adam convergence — the residual is bounded, so the *averaged*
gradient bias vanishes (Karimireddy et al., 2019).

Mechanics (inside a shard_map over the pod axis):
    t   = grad + err                 # fp32 accumulate with carried error
    q   = clip(round(t / scale), ±127).astype(int8);  scale = absmax/127
    wire: all_gather(q) + all_gather(scale)   # int8 on the DCN
    out = mean_pods(dequant(q))      # exact given the quantized operands
    err'= t - dequant(q)             # next step's carry

``ef_allreduce_tree`` applies this leaf-wise; ``make_compressed_grad_fn``
wraps a loss into a pod-sharded gradient function with the error state
threaded through the optimizer state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _quantize(t):
    absmax = jnp.max(jnp.abs(t))
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_allreduce(g, err, axis: str):
    """One leaf: int8-compressed mean over ``axis`` + new error carry.
    Runs inside shard_map; wire traffic is the int8 all_gather."""
    t = g.astype(jnp.float32) + err
    q, scale = _quantize(t)
    qg = jax.lax.all_gather(q, axis)                   # (n, ...) int8 wire
    sg = jax.lax.all_gather(scale, axis)               # (n,) f32 (tiny)
    deq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * g.ndim)
    mean = jnp.mean(deq, axis=0)
    new_err = t - q.astype(jnp.float32) * scale
    return mean.astype(g.dtype), new_err


def ef_allreduce_tree(grads, errs, axis: str):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(errs)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        m, ne = ef_allreduce(g, e, axis)
        out_g.append(m)
        out_e.append(ne)
    return (jax.tree.unflatten(treedef, out_g),
            jax.tree.unflatten(treedef, out_e))


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_compressed_grad_fn(loss_fn, mesh, *, axis: str = "pod"):
    """(params, batch, err) -> (loss, grads, err') with the cross-``axis``
    gradient reduction int8-compressed.

    params replicated over ``axis``; batch sharded over it (pure DP across
    pods).  Within-pod sharding stays with pjit around this function.
    """

    def per_pod(params, batch, err):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, err = ef_allreduce_tree(grads, err, axis)
        loss = jax.lax.pmean(loss, axis)
        return loss, grads, err

    batch_spec = jax.tree.map(lambda _: P(axis), {"tokens": 0, "labels": 0})
    return jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False)
