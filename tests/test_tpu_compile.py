"""Ahead-of-time compiles of the served DIN path for a described TPU v5e.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, at DIN's published widths and full vocabulary.
That catches what the TPU compiler refuses (tile alignment, VMEM budget,
a program that does not fit HBM) and whether the Pallas kernel is
compiled in (``tpu_custom_call``) — at no chip time.

The topology is described inside a fixture, never at import: only one
process may hold libtpu, and a module that touched it while being
collected would give pytest-xdist workers different test lists.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import registry
from repro.kernels.rerank_score.kernel import rerank_score_pallas
from repro.models.recsys import din

HBM_BYTES = 16 * 2**30           # one v5e chip
DIN = registry.get("din").config


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure: no topology
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _params(sharding):
    """Full-vocab DIN parameter shapes, placed on the described chip."""
    return _on(sharding, jax.eval_shape(
        functools.partial(din.init, cfg=DIN), jax.random.PRNGKey(0)))


def _user(sharding, B, T):
    i32 = jnp.int32
    return _on(sharding, {
        "fields": {f.name: jax.ShapeDtypeStruct(
            (B,) if f.bag == 1 else (B, f.bag), i32)
            for f in DIN.user_fields},
        "hist": jax.ShapeDtypeStruct((B, T), i32)})


def _items(sharding, n):
    return _on(sharding, {f.name: jax.ShapeDtypeStruct(
        (n,) if f.bag == 1 else (n, f.bag), jnp.int32)
        for f in DIN.item_fields})


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, m
    return total


@pytest.mark.parametrize("T", [8, 104])
def test_rerank_kernel_compiles_at_din_widths(one_chip, T):
    D, block_c = DIN.embed_dim, 128
    p = _params(one_chip)
    weights = [layer[k] for layer in (*p["attn_mlp"], *p["mlp"])
               for k in ("w", "b")]
    d_u = len(DIN.user_fields) * D
    d_i = (len(DIN.item_fields) - 1) * D
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    args = [f32((T, D)), f32((T,)), f32((block_c, D)), f32((d_u,)),
            f32((block_c, d_i)), *weights]
    kernel = functools.partial(rerank_score_pallas, block_c=block_c,
                               interpret=False)
    text = jax.jit(kernel).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_served_score_candidates_compiles_kernel(one_chip, monkeypatch):
    """The served re-rank program at its largest history bucket (T=100,
    padded to 104 inside the kernel) takes the compiled kernel once the
    device decision says TPU — both the impl and the interpret choice."""
    monkeypatch.setattr(kernels, "platform", lambda: "tpu")
    C, T = 64, DIN.seq_len
    fn = jax.jit(lambda p, u, c: din.score_candidates(p, u, c, DIN,
                                                      top_k=C))
    compiled = fn.lower(_params(one_chip), _user(one_chip, 1, T),
                        _items(one_chip, C)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_full_vocab_init_and_serve_fit_one_chip(one_chip):
    """The jitted init the scenario runtime uses, and the pointwise
    ``serve_scores`` at B=16, each fit one 16 GiB chip at full vocab."""
    init = jax.jit(din.init, static_argnums=1)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    init_bytes = _fits(init.lower(key, DIN).compile())
    assert init_bytes > 10 * 2**30      # the full tables, not a reduced cut
    serve = jax.jit(lambda p, b: din.serve_scores(p, b, DIN))
    batch = {"user": _user(one_chip, 16, DIN.seq_len),
             "item": _items(one_chip, 16)}
    _fits(serve.lower(_params(one_chip), batch).compile())
