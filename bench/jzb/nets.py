"""Small building blocks of the plain references: weight stacks, MLPs,
embedding bags and the matmuls at a stated precision.

``MATMULS`` maps a precision name to a matmul, written out so that each
computes the same on every backend. ``highest`` is float32, the plain
reference. ``fp8`` rounds both operands to float8 e4m3 and accumulates in
float32: the control for a configuration that serves its matmuls at
bf16, the nearest precision below it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def split(key, n: int) -> list:
    return [jax.random.fold_in(key, i) for i in range(n)]


def dense_stack(key, d_in: int, widths: tuple, mc) -> list:
    """Lecun-normal weights, N(0, bias_std) biases, in the program's
    ``[{"w": (d_in, d_out), "b": (d_out,)}, ...]`` layout."""
    out = []
    for k, w in zip(split(key, len(widths)), widths):
        kw, kb = split(k, 2)
        out.append({"w": jax.random.normal(kw, (d_in, w), jnp.float32)
                    * d_in ** -0.5,
                    "b": jax.random.normal(kb, (w,), jnp.float32)
                    * mc.bias_std})
        d_in = w
    return out


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def mm_fp8(a, b):
    return mm_highest(_fp8(a), _fp8(b))


MATMULS = {"highest": mm_highest, "fp8": mm_fp8}


def mlp_apply(layers: list, x, mm):
    for i, p in enumerate(layers):
        x = mm(x, p["w"]) + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.silu(x)
    return x


def bag_embed(table, ids, f):
    """ids (..., bag) → (..., D): the bag's rows summed, or averaged where
    the field says ``mean``."""
    out = table[ids].sum(-2)
    return out / f.bag if f.combiner == "mean" else out
