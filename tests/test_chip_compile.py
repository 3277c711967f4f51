"""Compile the main path's Pallas kernels, and the serving decode step,
for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot — unaligned blocks, scoped-VMEM overruns, primitives Mosaic does not
lower — at the widths the models run, with no chip.  The topology is
described only inside the fixture (never at import): one process at a time
may load the TPU library, and every test worker imports this file.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import GEMM, Configuration, Tile, codegen
from repro.kernels.attention import flash_attention
from repro.kernels.ssd import ssd_scan
from repro.models.model import build_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is in it
    return compiled


# InternLM2-1.8B attention: 16 query heads over 8 KV heads of width 128
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seq,block", [(512, 512), (2048, 512), (512, 128),
                                       (500, 128)])
def test_flash_attention_internlm2(one_chip, dtype, seq, block):
    q = jax.ShapeDtypeStruct((1, 16, seq, 128), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, seq, 128), dtype, sharding=one_chip)
    _compile(functools.partial(flash_attention, causal=True, block_q=block,
                               block_kv=block, interpret=False), q, kv, kv)


# Mamba2-130M SSD: 24 heads of width 64, state 128
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [256, 128])
def test_ssd_scan_mamba2_130m(one_chip, dtype, chunk):
    bh, seq, p, n = 24, 2048, 64, 128

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(functools.partial(ssd_scan, chunk=chunk, interpret=False),
             s(bh, seq, p), s(bh, seq, 1), s(bh, 1, 1), s(bh, seq, n),
             s(bh, seq, n))


def _gemm_args(one_chip):
    return {"A": jax.ShapeDtypeStruct((2000, 2600), jnp.float32,
                                      sharding=one_chip),
            "B": jax.ShapeDtypeStruct((2600, 2300), jnp.float32,
                                      sharding=one_chip)}


def test_gemm_128_tiles(one_chip):
    cfg = Configuration().child(Tile(loops=("i", "j", "k"),
                                     sizes=(128, 128, 128)))
    fn = codegen.build_pallas(GEMM, cfg.apply(GEMM.nest()), interpret=False)
    _compile(fn, _gemm_args(one_chip))


def test_gemm_unaligned_tiles_are_refused(one_chip):
    """100-wide blocks are not aligned to the (8, 128) vreg tiling: Mosaic
    refuses them, which the tuner records as a ``compile_error`` red node
    (interpret mode runs them)."""
    cfg = Configuration().child(Tile(loops=("i", "j", "k"),
                                     sizes=(100, 100, 100)))
    fn = codegen.build_pallas(GEMM, cfg.apply(GEMM.nest()), interpret=False)
    with pytest.raises(Exception, match="(?i)divisible|align|tiling|shape"):
        jax.jit(fn).lower(_gemm_args(one_chip)).compile()


def _hlo_ops(text):
    """{name: (shape, opcode, operand names)} of a compiled module's text."""
    ops = {}
    for m in re.finditer(r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)",
                         text):
        ops[m.group(1)] = (m.group(2), m.group(3),
                           re.findall(r"%(\S+?)(?:[,)]|$)", m.group(4)))
    return ops


# A decode step over 4 scanned layers at InternLM2-1.8B's and Mamba2-130M's
# widths (bf16, batch 8, max_seq 512), its caches donated as the engine
# donates them.  The TPU compiler picks the stacked caches' layout inside
# the layer loop, which decides whether they are copied whole.
@pytest.mark.parametrize("arch", ["internlm2_1_8b", "mamba2_130m"])
def test_decode_step_updates_stacked_caches_in_place(one_chip, arch):
    """No copy of a stacked cache, and into a stacked KV cache only the
    step's row is written."""
    cfg = dataclasses.replace(get_config(arch), n_layers=4, vocab_size=512)
    m = build_model(cfg)
    assert m.inplace_layers == 4

    def s(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(m.init, jax.random.key(0)))
    caches = jax.eval_shape(lambda: m.init_caches(8, 512, filled=3))
    text = jax.jit(m.decode_step, donate_argnums=(2,)).lower(
        params, s(jax.ShapeDtypeStruct((8, 1), jnp.int32)),
        jax.tree.map(s, caches),
        s(jax.ShapeDtypeStruct((8,), jnp.int32))).compile().as_text()

    leaves = jax.tree_util.tree_flatten_with_path(caches)[0]
    stacked = {",".join(map(str, x.shape)) for _, x in leaves if x.ndim >= 3}
    kv = {",".join(map(str, x.shape)) for path, x in leaves
          if getattr(path[-1], "name", None) in ("k", "v")}
    assert stacked and bool(kv) == (arch == "internlm2_1_8b")
    ops = _hlo_ops(text)
    copies = [n for n, (shape, op, _) in ops.items()
              if op == "copy" and shape in stacked]
    assert not copies, copies
    writes = [args for shape, op, args in ops.values()
              if op == "dynamic-update-slice" and shape in kv]
    assert len(writes) == 2 * bool(kv)          # K and V
    for args in writes:                         # (B, 1, KV, hd) into a layer
        assert ops[args[1]][0].split(",")[2] == "1", ops[args[1]]
