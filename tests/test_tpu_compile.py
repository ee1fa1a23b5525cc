"""The BP8 serving path's Pallas kernels compile for a TPU v5e.

Every other kernel test runs in interpret mode, which never shows the
kernels to Mosaic, the TPU kernel compiler.  Here each kernel is compiled
with ``interpret=False`` at h2o-danube-1.8B widths for one chip of a
described (not attached) ``v5e:2x2`` topology, and the program must hold
a Mosaic kernel (``tpu_custom_call``).  Mosaic refuses what interpret mode
accepts: scalar stores to VMEM, block shapes that break the (8, 128)
tiling, integer ``abs``, working sets larger than the scoped VMEM.

The paged cache's programs are compiled here too, at the benchmark
cells' pool widths: no copy in them relays out a whole pool leaf or
view.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.  Nothing here runs a kernel.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import attention as kattn
from repro.kernels import fused, ops

D_MODEL, D_FF, VOCAB = 2560, 6912, 32_000
KV_HEADS, GROUPS, HEAD_DIM = 8, 4, 80


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_absmax_compiles(one_chip):
    _compile_for_chip(lambda x: fused.absmax_pallas(x, interpret=False),
                      one_chip, ((256, D_MODEL), jnp.float32))


@pytest.mark.parametrize("n", [D_MODEL, D_FF, VOCAB])
@pytest.mark.parametrize("weights", ["real", "coded"])
def test_oisma_matmul_compiles(weights, n, one_chip):
    x = ((256, D_MODEL), jnp.float32)
    if weights == "real":
        _compile_for_chip(lambda x, w: ops.oisma_matmul(x, w, interpret=False),
                          one_chip, x, ((D_MODEL, n), jnp.float32))
    else:
        _compile_for_chip(
            lambda x, c, s: ops.oisma_matmul(x, c, y_scale=s,
                                             interpret=False),
            one_chip, x, ((D_MODEL, n), jnp.int8), ((1, 1), jnp.float32))


def test_oisma_matmul_unfused_compiles(one_chip):
    _compile_for_chip(
        lambda x, w: ops.oisma_matmul(x, w, impl="unfused", interpret=False),
        one_chip, ((8, D_MODEL), jnp.float32), ((D_MODEL, D_MODEL),
                                                jnp.float32))


def test_oisma_mlp_compiles(one_chip):
    w = ((D_MODEL, D_FF), jnp.float32)
    _compile_for_chip(
        lambda x, u, g: ops.oisma_mlp(x, u, g, interpret=False),
        one_chip, ((256, D_MODEL), jnp.float32), w, w)


@pytest.mark.parametrize("s", [4096, 64])
def test_bp8_decode_attention_compiles(s, one_chip):
    b = 4
    codes = ((b, s, KV_HEADS, HEAD_DIM), jnp.int8)
    scales = ((b, s, KV_HEADS), jnp.float32)
    _compile_for_chip(
        lambda *a: kattn.bp8_decode_attention(*a, interpret=False),
        one_chip, ((b, KV_HEADS, GROUPS, HEAD_DIM), jnp.float32),
        codes, scales, codes, scales, ((b, s), jnp.int32), ((b,), jnp.int32),
        ((), jnp.int32))


def test_held_expert_grouped_matmuls_compile(one_chip):
    """DeepSeek-V2's serving MoE layer at published widths, one chip's 20
    of 160 experts in each of 6 layers: the grouped matmuls are Mosaic
    kernels that read the layer's experts from the stacked weights, with
    no copy of one layer's experts out of the stack."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe

    cfg = dataclasses.replace(get_config("deepseek_v2_236b"), experts_held=20)
    layers, rows = 6, 64

    def layer(x, p, i):
        return moe.moe_apply(dict(p, layer=i), cfg, x, dropless=True)["out"]

    defs = moe.moe_defs(cfg)
    args = [jax.ShapeDtypeStruct((rows, 1, cfg.d_model), jnp.bfloat16,
                                 sharding=one_chip),
            {k: jax.ShapeDtypeStruct(
                ((layers,) if k in moe.EXPERT_WEIGHTS else ()) + d.shape,
                d.dtype, sharding=one_chip) for k, d in defs.items()},
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    text = jax.jit(layer).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "bf16[20,5120,1536]" not in text and "bf16[20,1536,5120]" not in text


# the benchmark cells' pools: (preset, changed sizes), slots, blocks of 16
POOLS = {"danube": (("h2o_danube_1p8b", {}), 16, 1537),
         "deepseek_v2": (("deepseek_v2_236b", {"num_layers": 7}), 64, 6145)}
BLOCK = 16


HLO_TYPES = {"bfloat16": "bf16", "float32": "f32", "int32": "s32",
             "int8": "s8"}


def _hlo_shape(x):
    return (f"{HLO_TYPES[jnp.dtype(x.dtype).name]}"
            f"[{','.join(map(str, x.shape))}]")


def _abstract_cache(cfg, slots, blocks):
    """A ``PagedCache`` whose pool is shapes only, and whose programs,
    when its public methods call them, record their arguments and give
    back shapes: ``(cache, calls)``, ``calls`` by program name."""
    from repro.models import build
    from repro.serve.paged_cache import PagedCache

    made = []

    def make():
        made.append(PagedCache(build(cfg), slots=slots,
                               num_blocks=blocks, block_size=BLOCK))
        return made[0]._pool

    pool = jax.eval_shape(make)
    cache, calls = made[0], {}
    cache._pool = pool
    names = {id(fn): name for name, fn in cache.programs().items()}
    for attr, fn in list(vars(cache).items()):
        if id(fn) in names:
            def record(*args, fn=fn, name=names[id(fn)]):
                calls[name] = (fn, args)
                return jax.eval_shape(fn, *args)
            setattr(cache, attr, record)
    return cache, calls


def _relayouts(text):
    """(result shape, result layout, operand layout) of each copy in an
    HLO module whose layout differs from its operand's; a copy between
    memory spaces (``S(n)``) alone keeps its layout."""
    layout = {m[0]: m[1] for m in re.findall(
        r"%([\w.\-]+) = \w+\[[\d,]*\](\{[^}]*\})", text)}
    strip = lambda lay: re.sub(r"S\(\d+\)", "", lay or "")
    out = []
    for shape, lay, arg in re.findall(
            r"= (\w+\[[\d,]*\])(\{[^}]*\}) copy\(%([\w.\-]+)\)", text):
        if strip(lay) != strip(layout.get(arg)):
            out.append((shape, lay, layout.get(arg)))
    return out


# rows None: one a slot, as a decode step gathers and commits them
@pytest.mark.parametrize("program,rows,view,chunk", [
    ("cache_gather", None, 1024, 0), ("cache_gather", 1, 512, 0),
    ("cache_commit_decode", None, 1024, 0),
    ("cache_commit_prefill", 1, 1024, 512), ("cache_scrub", 0, 0, 0)])
@pytest.mark.parametrize("pool", list(POOLS))
def test_cache_programs_keep_pool_layout(pool, program, rows, view, chunk,
                                         one_chip):
    """Each of the paged cache's programs, called as ``PagedCache``'s
    public methods call it, copies no whole pool leaf and relays out no
    whole view: the pool and the views keep their default layouts in and
    out, and every piece is sliced out of them in place."""
    from repro.configs import get_config

    (arch, sizes), slots, blocks = POOLS[pool]
    cfg = dataclasses.replace(get_config(arch), **sizes)
    cache, calls = _abstract_cache(cfg, slots, blocks)
    rows = slots if rows is None else rows
    for slot in range(max(rows, 1)):
        cache.alloc_slot(slot, max(view, 1024) // BLOCK)
    views = []
    if rows:
        views = jax.tree.leaves(cache.gather(list(range(rows)), view))
    if program == "cache_commit_decode":
        cache.commit_decode(jax.tree.unflatten(
            jax.tree.structure(cache.gather(list(range(rows)), view)), views),
            list(range(rows)), list(range(rows)), [view - 1] * rows)
    elif program == "cache_commit_prefill":
        cache.commit_prefill(jax.tree.unflatten(
            jax.tree.structure(cache.gather([0], view)), views),
            0, view - chunk, chunk)
    elif program == "cache_scrub":
        cache.free_slot(0)
    fn, args = calls[program]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), x.dtype, sharding=one_chip), args)
    text = fn.lower(*args).compile().as_text()
    copies = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} copy\(", text)
    whole = {_hlo_shape(x) for x in cache._pool}
    assert not whole & set(copies), sorted(whole & set(copies))
    whole_views = {_hlo_shape(x) for x in views}
    assert not [c for c in _relayouts(text) if c[0] in whole_views]
