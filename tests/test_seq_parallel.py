"""Sequence parallelism: the ring-attention core, the `get_rules` preset
registry that fronts it, and the declarative roofline MeshSpec.

In-process tests cover the registry contract (every phase registered, the
deprecated free functions warn and delegate), the single-device ring
oracle against dense SDPA, and the ring hand-off term in the roofline.
Multi-device numerics (ring == oracle BITWISE on an 8-device seq mesh,
composed with TP, through the real attention layers) run in subprocesses
because XLA_FLAGS must be set before jax initialises.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _compat import given, settings, st
from repro.launch.mesh import make_mesh
from repro.dist import sharding as shd
from repro.models import attention as A
from repro.roofline.model import MeshAxis, MeshSpec, SINGLE_POD

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run_sub(script, timeout=900):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# the get_rules registry
# ---------------------------------------------------------------------------

PHASES = ("train", "prefill", "decode", "pipeline", "dp_only", "sequence",
          "sp")
MESH_AXIS_VOCAB = {"pod", "seq", "data", "model", "stage"}
ALIASES = [
    ("train_rules", "train", {}),
    ("prefill_rules", "prefill", {}),
    ("decode_rules", "decode", {"batch": 1, "data_size": 16}),
    ("decode_rules", "decode", {"batch": 256, "data_size": 16}),
    ("pipeline_rules", "pipeline", {}),
    ("dp_only_rules", "dp_only", {}),
]


def test_registry_phases_complete():
    assert set(PHASES) <= set(shd.rule_phases())
    for ph in PHASES:
        assert isinstance(shd.get_rules(ph), shd.Rules), ph


def test_unknown_phase_raises():
    with pytest.raises(ValueError, match="unknown parallelism phase"):
        shd.get_rules("warp")


def test_get_rules_returns_fresh_copies():
    a = shd.get_rules("train")
    a["batch"] = "model"
    assert shd.get_rules("train")["batch"] == ("pod", "data")


def test_sequence_preset_is_registry_only():
    # no free-function alias (it postdates the deprecation of that style)
    # and no --rules CLI exposure (it needs a seq-bearing mesh, not just a
    # rules swap; the dry-run engages it through --seq)
    assert "sequence" not in shd.RULE_PRESETS
    assert not hasattr(shd, "sequence_rules")
    rules = shd.get_rules("sequence")
    assert rules.mesh_axes("kv_seq") == ("seq",)
    assert rules.mesh_axes("seq") == ("seq",)
    assert "seq" in rules.mesh_axes("ffn")  # weights fold over idle seq


def test_deprecated_aliases_warn_and_match_registry():
    for name, phase, kw in ALIASES:
        with pytest.warns(DeprecationWarning, match=name):
            got = getattr(shd, name)(**kw)
        assert got == shd.get_rules(phase, **kw), (name, kw)


def test_rule_presets_values_are_the_aliases():
    # pre-registry identity assertions elsewhere in the suite depend on it
    assert shd.RULE_PRESETS["pipeline"] is shd.pipeline_rules
    assert shd.RULE_PRESETS["sp"] is shd.train_rules


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(PHASES))
def test_phase_axes_within_mesh_vocabulary(phase):
    """Every mesh axis any preset names must exist on some production
    mesh — a rule naming an unknown axis would silently replicate."""
    rules = shd.get_rules(phase)
    for logical in rules:
        assert set(rules.mesh_axes(logical)) <= MESH_AXIS_VOCAB, logical


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=1, max_value=512),
       st.integers(min_value=1, max_value=32))
def test_decode_alias_equals_registry_for_any_geometry(batch, data_size):
    rules = shd.get_rules("decode", batch=batch, data_size=data_size)
    folded = data_size > 1 and (batch < data_size or batch % data_size)
    assert rules.mesh_axes("heads") == (
        ("data", "model") if folded else ("model",))
    with pytest.warns(DeprecationWarning):
        alias = shd.decode_rules(batch=batch, data_size=data_size)
    assert alias == rules


# ---------------------------------------------------------------------------
# declarative MeshSpec
# ---------------------------------------------------------------------------

def test_meshspec_compat_constructor():
    assert MeshSpec(1, 16, 16) == SINGLE_POD        # positional, old order
    spec = MeshSpec(pod=2, data=16, model=16)
    assert (spec.chips, spec.dp, spec.weight_shards) == (512, 32, 16)
    piped = MeshSpec(data=4, model=16, stage=4)
    assert piped.weight_shards == 64                # tensor x stage


def test_meshspec_seq_axis():
    spec = MeshSpec(data=1, model=16, seq=16)
    assert spec.seq == 16 and spec.chips == 256
    assert spec.dp == 1                  # "seq" is sequence, not batch
    assert spec.weight_shards == 16      # nor tensor
    assert spec.role_size("sequence") == 16


def test_meshspec_from_axes():
    spec = MeshSpec.from_axes([("seq", 4, "sequence"), ("data", 2, "batch"),
                               MeshAxis("model", 2, "tensor")])
    assert spec.chips == 16
    assert spec.axis_size("seq") == 4
    assert spec.axis_size("absent") == 1
    with pytest.raises(ValueError, match="duplicate"):
        MeshSpec.from_axes([("data", 2, "batch"), ("data", 4, "batch")])


def test_roofline_prices_ring_handoff():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.roofline.model import cell_collective_bytes

    cfg = get_config("qwen2_72b")
    shape = SHAPES["long_500k"]
    seq_mesh = MeshSpec(data=1, model=16, seq=16)
    coll = cell_collective_bytes(cfg, shape, seq_mesh)
    # stats schedule: (n-1) hops x per-layer (m, l, acc) tuple, f32
    expect = (15 * cfg.num_layers * shape.global_batch * cfg.num_heads
              * (cfg.head_dim + 2) * 4)
    assert coll["ring_permute"] == expect
    # no ring -> no term
    assert "ring_permute" not in cell_collective_bytes(cfg, shape, SINGLE_POD)
    # MLA rings the latent, not per-head values
    mla = get_config("deepseek_v2_236b")
    coll = cell_collective_bytes(mla, shape, seq_mesh)
    assert coll["ring_permute"] == (15 * mla.num_layers * shape.global_batch
                                    * mla.num_heads * (mla.kv_lora_rank + 2)
                                    * 4)


def test_shape_applicable_seq_gate():
    from repro.configs import get_config, shape_applicable
    from repro.configs.base import SHAPES

    full, sub = get_config("qwen2_72b"), get_config("zamba2_2p7b")
    long = SHAPES["long_500k"]
    assert not shape_applicable(full, long)[0]
    assert not shape_applicable(full, long, seq_shards=1)[0]
    assert shape_applicable(full, long, seq_shards=16)[0]
    assert shape_applicable(sub, long)[0]
    assert shape_applicable(full, SHAPES["decode_32k"], seq_shards=1)[0]


# ---------------------------------------------------------------------------
# single-device ring numerics (the oracle itself)
# ---------------------------------------------------------------------------

def _toy(b=2, sq=16, h=8, kh=4, d=16, skv=64, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(skv - sq, skv)[None], (b, sq))
    kv_pos = jnp.broadcast_to(jnp.arange(skv)[None], (b, skv))
    return q, k, v, q_pos, kv_pos


@pytest.mark.parametrize("n_blocks", [1, 2, 4, 8])
def test_ring_reference_matches_sdpa(n_blocks):
    q, k, v, q_pos, kv_pos = _toy()
    ref = A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=n_blocks,
                           causal=True)
    dense = A.sdpa(q, k, v, q_pos, kv_pos, causal=True)
    assert float(jnp.abs(ref - dense).max()) < 1e-5
    # block count must not change the merge (canonical order)
    one = A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=1, causal=True)
    assert float(jnp.abs(ref - one).max()) < 1e-5


def test_ring_reference_softcap_and_window():
    q, k, v, q_pos, kv_pos = _toy()
    for kw in ({"softcap": 30.0}, {"window": 24}):
        ref = A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=4,
                               causal=True, **kw)
        dense = A.sdpa(q, k, v, q_pos, kv_pos, causal=True, **kw)
        assert float(jnp.abs(ref - dense).max()) < 1e-5, kw


def test_ring_reference_rejects_indivisible():
    q, k, v, q_pos, kv_pos = _toy(skv=60)
    with pytest.raises(ValueError, match="not divisible"):
        A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=8)


def test_pad_kv_is_exact():
    """Padded slots carry position -1 and are wiped by the merge."""
    from repro.dist.seq import pad_kv
    q, k, v, q_pos, kv_pos = _toy(skv=60)
    kp, vp, pp = pad_kv(k, v, kv_pos, 64)
    assert kp.shape[1] == 64 and int(pp[0, -1]) == -1
    ref = A.ring_reference(q, kp, vp, q_pos, pp, n_blocks=4, causal=True)
    dense = A.sdpa(q, k, v, q_pos, kv_pos, causal=True)
    assert float(jnp.abs(ref - dense).max()) < 1e-5


def test_ring_noop_outside_context():
    """Without use_ring (or with rules that never shard kv_seq),
    ring_attend declines and callers fall back to dense sdpa."""
    from repro.dist import seq as msq
    q, k, v, q_pos, kv_pos = _toy()
    assert msq.ring_attend(q, k, v, q_pos, kv_pos) is None
    n = len(jax.devices())
    mesh = make_mesh((1, n), ("data", "model"))
    with pytest.raises(ValueError, match="no 'seq' axis"):
        msq.use_ring(mesh).__enter__()
    with shd.use_rules(mesh, shd.get_rules("prefill")):
        assert msq.ring_attend(q, k, v, q_pos, kv_pos) is None


# ---------------------------------------------------------------------------
# multi-device numerics (8 host devices, subprocess)
# ---------------------------------------------------------------------------

RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.dist import seq as msq
from repro.dist import sharding as shd
from repro.models import attention as A

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("seq", "data"))
rules = shd.get_rules("sequence")
rng = np.random.default_rng(0)
b, sq, h, kh, d, skv = 2, 32, 8, 4, 16, 64
q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
k = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
v = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
q_pos = jnp.broadcast_to(jnp.arange(skv - sq, skv)[None], (b, sq))
kv_pos = jnp.broadcast_to(jnp.arange(skv)[None], (b, skv))

# prefill-style: q sharded over the ring -> KV blocks rotate
with shd.use_rules(mesh, rules), msq.use_ring(mesh):
    out = msq.ring_attend(q, k, v, q_pos, kv_pos)
assert out is not None
ref = A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=4, causal=True)
assert jnp.array_equal(out, ref), "kv-rotation not bitexact vs oracle"
dense = A.sdpa(q, k, v, q_pos, kv_pos, causal=True)
assert float(jnp.abs(out - dense).max()) < 1e-5
print("RING_KV_BITEXACT")

# decode-style: q replicated across the ring -> the stats tuple rotates
q1, qp1 = q[:, -1:], q_pos[:, -1:]
with shd.use_rules(mesh, rules), msq.use_ring(mesh):
    out1 = msq.ring_attend(q1, k, v, qp1, kv_pos)
ref1 = A.ring_reference(q1, k, v, qp1, kv_pos, n_blocks=4, causal=True)
assert jnp.array_equal(out1, ref1), "stats-rotation not bitexact vs oracle"
print("RING_STATS_BITEXACT")

# the two schedules are bitwise-identical on identical inputs (same
# partials into the same canonical merge; only the travelling tensor
# differs) — compare them directly with q replicated in both
from jax.sharding import PartitionSpec as P
kspec = P(None, "seq", None, None)
qspec = P(None, None, None, None)

def run(rot):
    f = jax.shard_map(
        lambda qb, kb, vb, qp, kp: A.ring_sdpa(
            qb, kb, vb, qp, kp, axis_name="seq", n_blocks=4, rotate=rot,
            causal=True),
        mesh=mesh, in_specs=(qspec, kspec, kspec, P(None, None),
                             P(None, "seq")),
        out_specs=qspec, check_vma=False)
    return f(q1, k, v, qp1, kv_pos)

assert jnp.array_equal(run("kv"), run("stats")), "schedules disagree bitwise"
print("RING_SCHEDULES_AGREE")

# odd remainder: skv=59 % ring=4 != 0 rides the ring via pad_kv (the
# spec derivation probes the rounded-up length; padded slots carry
# position -1 and are wiped exactly by the merge)
k2, v2, kp2 = k[:, :59], v[:, :59], kv_pos[:, :59]
with shd.use_rules(mesh, rules), msq.use_ring(mesh):
    out2 = msq.ring_attend(q1, k2, v2, qp1, kp2)
d2 = A.sdpa(q1, k2, v2, qp1, kp2, causal=True)
assert float(jnp.abs(out2 - d2).max()) < 1e-5
print("RING_REMAINDER_OK")

# absorbed-MLA ring over a seq-sharded latent cache
r, p_dim, hh = 24, 8, 6
qa = jnp.asarray(rng.normal(size=(b, 1, hh, r)), jnp.float32)
qr = jnp.asarray(rng.normal(size=(b, 1, hh, p_dim)), jnp.float32)
ckv = jnp.asarray(rng.normal(size=(b, skv, r)), jnp.float32)
kr = jnp.asarray(rng.normal(size=(b, skv, p_dim)), jnp.float32)
with shd.use_rules(mesh, rules), msq.use_ring(mesh):
    ol = msq.ring_attend_mla(qa, qr, ckv, kr, qp1, kv_pos, scale=0.17)
olr = A.ring_mla_reference(qa, qr, ckv, kr, qp1, kv_pos, n_blocks=4,
                           scale=0.17)
assert jnp.array_equal(ol, olr), "MLA ring not bitexact vs oracle"
print("RING_MLA_BITEXACT")
"""


def test_ring_attention_8dev_bitexact():
    out = _run_sub(RING_SCRIPT)
    for tag in ("RING_KV_BITEXACT", "RING_STATS_BITEXACT",
                "RING_SCHEDULES_AGREE", "RING_REMAINDER_OK",
                "RING_MLA_BITEXACT"):
        assert tag in out


TP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.dist import seq as msq
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import attention as A

# ring composed with tensor parallelism: (seq=2, data=2, model=2)
mesh = make_host_mesh(model=2, seq=2)
assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
    "seq": 2, "data": 2, "model": 2}
rules = shd.get_rules("sequence")
rng = np.random.default_rng(1)
b, sq, h, kh, d, skv = 2, 8, 8, 2, 16, 32
q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
k = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
v = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
q_pos = jnp.broadcast_to(jnp.arange(skv - sq, skv)[None], (b, sq))
kv_pos = jnp.broadcast_to(jnp.arange(skv)[None], (b, skv))
with shd.use_rules(mesh, rules), msq.use_ring(mesh):
    out = msq.ring_attend(q, k, v, q_pos, kv_pos)
assert out is not None
ref = A.ring_reference(q, k, v, q_pos, kv_pos, n_blocks=2, causal=True)
assert jnp.array_equal(out, ref), "ring x TP not bitexact vs oracle"
print("RING_TP_BITEXACT")
"""


def test_ring_composes_with_tp_8dev():
    out = _run_sub(TP_SCRIPT)
    assert "RING_TP_BITEXACT" in out


MODEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.dist import seq as msq
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import attention as A
from repro.models.params import empty_cache, init_tree

mesh = make_host_mesh(model=2, seq=4)   # (4, 1, 2)
rules = shd.get_rules("sequence")
rng = np.random.default_rng(2)
b, L = 2, 48

def decode_both(apply_prefill, apply_decode):
    '''Prefill L-1 tokens into a cache, then decode token L-1 with the
    ring on vs. off; the attention layers pick the path themselves.'''
    pos = jnp.arange(L - 1)
    cache = apply_prefill(pos)
    x1 = jnp.asarray(rng.normal(size=(b, 1, dm)), jnp.float32)
    p1 = jnp.full((b, 1), L - 1)
    with shd.use_rules(mesh, rules), msq.use_ring(mesh):
        ring, _ = apply_decode(x1, p1, cache)
    plain, _ = apply_decode(x1, p1, cache)
    return ring, plain

# --- GQA (qwen2-72b miniature: full attention, grouped heads) ---
cfg = dataclasses.replace(get_config("qwen2_72b", smoke=True),
                          num_heads=8, num_kv_heads=4)
dm = cfg.d_model
params = init_tree(A.gqa_defs(cfg, jnp.float32), jax.random.key(0))
x = jnp.asarray(rng.normal(size=(b, L - 1, dm)), jnp.float32)
spec = A.kv_cache_spec(cfg, b, L)

def gqa_prefill(pos):
    _, cache = A.gqa_apply(params, cfg, x, pos, window=None,
                           cache=empty_cache(spec))
    return cache

def gqa_decode(x1, p1, cache):
    return A.gqa_apply(params, cfg, x1, p1, window=None, cache=cache)

ring, plain = decode_both(gqa_prefill, gqa_decode)
err = float(jnp.abs(ring - plain).max())
assert err < 1e-4, f"GQA ring decode diverged: {err}"
print("MODEL_GQA_OK", err)

# --- MLA (minicpm3 miniature: absorbed decode over the latent cache) ---
cfg = get_config("minicpm3_4b", smoke=True)
dm = cfg.d_model
params = init_tree(A.mla_defs(cfg, jnp.float32), jax.random.key(1))
x = jnp.asarray(rng.normal(size=(b, L - 1, dm)), jnp.float32)
spec = A.kv_cache_spec(cfg, b, L)

def mla_prefill(pos):
    _, cache = A.mla_apply(params, cfg, x, pos, cache=empty_cache(spec))
    return cache

def mla_decode(x1, p1, cache):
    return A.mla_apply(params, cfg, x1, p1, cache=cache)

ring, plain = decode_both(mla_prefill, mla_decode)
err = float(jnp.abs(ring - plain).max())
assert err < 1e-4, f"MLA ring decode diverged: {err}"
print("MODEL_MLA_OK", err)
"""


def test_attention_layers_ring_equals_dense_8dev():
    """End to end through gqa_apply / mla_apply: a decode step with the
    ring engaged (sequence rules + use_ring on a (4, 1, 2) mesh) matches
    the same step on the dense single-path fallback."""
    out = _run_sub(MODEL_SCRIPT)
    assert "MODEL_GQA_OK" in out and "MODEL_MLA_OK" in out


SMOKE_SHAPES_SCRIPT = r"""
from repro.launch.dryrun import SMOKE_SHAPES, smoke_shapes
from repro.configs.base import SHAPES

# the satellite bugfix: smoke long_500k derives from the canonical shape
# (it used to re-declare seq_len=2048 as an unrelated literal)
for name, s in SMOKE_SHAPES.items():
    canon = SHAPES[name]
    assert (s.name, s.kind) == (canon.name, canon.kind)
assert SMOKE_SHAPES["long_500k"].global_batch == SHAPES["long_500k"].global_batch
assert SMOKE_SHAPES["long_500k"].seq_len == 2048
assert smoke_shapes(proxy_seq=4096)["long_500k"].seq_len == 4096
assert smoke_shapes(proxy_seq=4096)["train_4k"] == SMOKE_SHAPES["train_4k"]
print("SMOKE_SHAPES_OK")
"""


def test_smoke_shapes_derive_from_canonical():
    # subprocess: importing repro.launch.dryrun forces 512 host devices
    out = _run_sub(SMOKE_SHAPES_SCRIPT)
    assert "SMOKE_SHAPES_OK" in out
