"""Compile the main path's kernels for a DESCRIBED v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached (guides: on-chip-measurement, section 2): what
it refuses — a block the (8, 128) tiling cannot hold, more SMEM/VMEM
than a kernel may use — it would refuse on the chip, and interpret mode
never sees it. These are the kernels ``chip_smoke.py`` dispatches, at
Mistral-7B widths (H32 / KV8 / D128, hidden 4096, pages of 16), about
two seconds each, and the latent pool's step and the routed-experts
program at Xing4.0-29B-A4B's widths. Nothing runs: a compile that passes
is not a chip run.

This is the ONLY file that describes the chip, and it does so inside a
fixture: one process at a time may load libtpu, xdist workers each
import every test file, and a topology call at import would give the
workers different tests to collect (the whole suite then counts 0).
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KVH, D, E, PAGE = 32, 8, 128, 4096, 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *specs):
    """Lower ``fn`` over (shape, dtype) specs placed on the described
    chip and compile it; returns the compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pallas_calls(jaxpr, name):
    """The pallas calls of that name in a program, nested programs
    opened."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            if e.params["name"] == name:
                found.append(e)
            continue
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                found += _pallas_calls(sub, name)
    return found


def _ragged_specs(b, t, npages, max_pages, kv_dtype, kvh=KVH):
    specs = [((b, t, H, D), BF16),
             ((npages, PAGE, kvh, D), kv_dtype),
             ((npages, PAGE, kvh, D), kv_dtype),
             ((b, max_pages), jnp.int32), ((b,), jnp.int32),
             ((b,), jnp.int32)]
    if kv_dtype == jnp.int8:
        specs += [((npages, KVH), jnp.float32)] * 2
    return specs


def _pool_sized(text, npages, kvh=KVH):
    """The operations of a compiled program whose result has the pool's
    element count, parameters aside: [(shape, layout, line)]."""
    import re

    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\](\{[\d,]*)", line)
        if not m or " parameter(" in line:
            continue
        dims = [int(x) for x in m.group(1).split(",")]
        if math.prod(dims) == npages * PAGE * kvh * D:
            found.append((dims, m.group(2), line.strip()[:160]))
    return found


# at 8 KV heads, the last three: mistral-7b-serve.decode-closed32's own
# calls (32 rows, 4,096 pages, tables 32-128 wide, a step with a 64-token
# prompt chunk); at 32 KV heads and group 1 (the kernel's few-row form):
# evabyte-6.5b-serve.docs-closed24's, decode rows alone and beside a
# prompt chunk padded to 512 tokens, where the kernel holds both forms
@pytest.mark.parametrize("b,t,max_pages,window,kvh", [
    (8, 1, 128, 0, KVH), (8, 1, 128, 4096, KVH), (8, 16, 128, 4096, KVH),
    (8, 64, 128, 0, KVH), (8, 64, 128, 4096, KVH), (1, 64, 16, 4096, KVH),
    (64, 1, 256, 4096, KVH),
    (32, 1, 32, 4096, KVH), (32, 1, 128, 4096, KVH),
    (32, 64, 64, 4096, KVH),
    (32, 1, 128, 0, 32), (32, 1, 256, 0, 32), (32, 512, 256, 0, 32),
])
def test_ragged_bf16(one_chip, b, t, max_pages, window, kvh):
    from paddle_tpu.ops.kernels.paged_attention import \
        paged_ragged_attention

    npages = 4096 if b >= 32 else 2048

    def f(q, kp, vp, tbl, lens, ql):
        return paged_ragged_attention(q, kp, vp, tbl, lens, q_lens=ql,
                                      window=window, interpret=False)

    text = _compile(f, one_chip,
                    *_ragged_specs(b, t, npages, max_pages, BF16, kvh))
    assert "tpu_custom_call" in text
    # the pages reach the kernel as the pool holds them: under the
    # chip's tiling a transpose of the pool, and the lane-merged view
    # (pages, 16, kv heads * head_dim) too, is a copy of all of it
    assert _pool_sized(text, npages, kvh) == []


@pytest.mark.parametrize("t", [1, 16])
def test_ragged_int8_kv_2048_pages(one_chip, t):
    """The per-page scale sidecars ride scalar prefetch flattened: as
    2-D (2048, 8) SMEM operands they padded to 1 MiB each and the
    compiler refused the kernel (2.01M of 1.00M SMEM)."""
    from paddle_tpu.ops.kernels.paged_attention import \
        paged_ragged_attention

    def f(q, kp, vp, tbl, lens, ql, ks, vs):
        return paged_ragged_attention(
            q, kp, vp, tbl, lens, q_lens=ql, window=4096,
            k_scales=ks, v_scales=vs, interpret=False)

    text = _compile(f, one_chip,
                    *_ragged_specs(8, t, 2048, 128, jnp.int8))
    assert "tpu_custom_call" in text


FFN = 14336


def _layer_specs(n_pad, b_pad, t_pad, mp, npages):
    i32, pool = jnp.int32, ((npages, PAGE, KVH, D), BF16)
    return [pool, pool, ((n_pad, E), BF16), ((E,), BF16),
            ((E, H * D), BF16), ((E, KVH * D), BF16),
            ((E, KVH * D), BF16), ((H * D, E), BF16), ((E,), BF16),
            ((E, FFN), BF16), ((E, FFN), BF16), ((FFN, E), BF16),
            ((32768, D), jnp.float32), ((32768, D), jnp.float32),
            ((5, n_pad), i32), ((b_pad, t_pad), i32), ((2, n_pad), i32),
            ((b_pad, mp + 2), i32)]


def _layer_cfg(n_pad, b_pad, t_pad, mp, npages):
    return (n_pad, E, H, KVH, D, npages, PAGE, b_pad, t_pad, mp,
            D ** -0.5, 4096, False, 1e-5, False)


# mistral-7b-serve.decode-closed32's steps (32 rows, 4,096 pages, tables
# 32 / 64 / 128 wide, 32 decode rows or 31 beside a 64-token prompt
# chunk) and one serving bucket of 8 rows
@pytest.mark.parametrize("n_pad,b_pad,t_pad,mp,npages", [
    (128, 8, 64, 128, 2048),
    (32, 32, 1, 32, 4096), (32, 32, 1, 64, 4096), (32, 32, 1, 128, 4096),
    (128, 32, 64, 64, 4096),
])
def test_layer_program(one_chip, monkeypatch, n_pad, b_pad, t_pad, mp,
                       npages):
    """A decoder layer of the packed step as ONE program, its pools
    donated: norm (the Mosaic ``rms_norm``), qkv + RoPE + page scatter,
    ONE ragged kernel call, o_proj, residual, norm, the gated MLP,
    residual. Both pools come back in the buffers they came in (aliased
    input to output, the page write in place) and nothing copies,
    transposes or reshapes a pool for the kernel."""
    import re

    import paddle_tpu.ops.kernels as kernels
    from paddle_tpu.ops.kernels.paged_attention import _build_layer_call

    # the backend here is the CPU: take the chip's branch of the norm
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    run = _build_layer_call(*_layer_cfg(n_pad, b_pad, t_pad, mp, npages))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _layer_specs(n_pad, b_pad, t_pad, mp, npages)]
    text = jax.jit(run, donate_argnums=(0, 1)).lower(
        *args).compile().as_text()
    head = text.splitlines()[0]
    # outputs 1 and 2 (the pools) alias parameters 0 and 1
    assert re.search(r"\{1\}: \(0, \{\}", head), head[:300]
    assert re.search(r"\{2\}: \(1, \{\}", head), head[:300]
    pools = _pool_sized(text, npages)
    assert pools and all(
        tuple(dims) == (npages, PAGE, KVH, D) and layout == "{3,2,1,0"
        for dims, layout, _ in pools)
    for _, _, line in pools:
        op = re.search(r"\} (\w[\w-]*)\(", line).group(1)
        assert op in ("scatter", "fusion"), line      # the page write
    assert text.count("tpu_custom_call") == 3    # two norms, one attend
    assert len(re.findall(r"ragged_paged_attention/pallas_call",
                          text)) == 1


def test_layer_program_is_one_for_every_layer():
    """The layers' weights are operands of the layer program: its jaxpr
    closes over no array (a weight closed over would make the program a
    layer's own) and the cache that holds it has no layer in its key, so
    a second layer's call at the same shapes builds nothing."""
    import importlib

    pa = importlib.import_module("paddle_tpu.ops.kernels.paged_attention")
    shape = (32, 32, 1, 64, 4096)
    cfg = _layer_cfg(*shape)[:-1] + (True,)          # interpret: no chip
    specs = [jax.ShapeDtypeStruct(s, d) for s, d in _layer_specs(*shape)]
    closed = jax.make_jaxpr(pa._build_layer_call(*cfg))(*specs)
    assert [c.shape for c in closed.consts if c.size > 1] == []
    assert len(closed.jaxpr.invars) == len(specs)
    first = pa._jitted_layer_step(cfg, False, None)
    misses = pa._jitted_layer_step.cache_info().misses
    assert pa._jitted_layer_step(cfg, False, None) is first
    assert pa._jitted_layer_step.cache_info().misses == misses
    # two layers' operands have one signature: one trace, one program
    jaxprs = {str(jax.make_jaxpr(first)(*specs)) for _ in range(2)}
    assert len(jaxprs) == 1


# sha256 of the layer program's jaxpr at two of the Mistral cell's shapes,
# taken from the parent of PR 34 (commit 4c5301f), before the program had
# its two switches; PR 35's few-row form of the kernel inside is off at
# Mistral's group of 4 and leaves them as they were
MISTRAL_BODY = {
    (32, 32, 1, 64, 4096):
        "6242de8a09a1409080944790e622b948a9c54cc1df179daf79291a45066ee8ee",
    (128, 32, 64, 64, 4096):
        "10a368f96885397a3b706bd721308c86082365c587796ad1c3ffc40c26124404",
}


@pytest.mark.parametrize("shape", sorted(MISTRAL_BODY))
def test_mistral_layer_program_keeps_its_body(shape):
    """The layer program's two static switches (the norms' unit offset,
    the summary epilogue) are off for a Llama/Mistral layer and leave its
    program as it was: the jaxpr is, letter for letter, the one the
    program had before it knew of them."""
    import hashlib
    import importlib

    pa = importlib.import_module("paddle_tpu.ops.kernels.paged_attention")
    cfg = _layer_cfg(*shape)[:-1] + (True,)          # interpret: no chip
    specs = [jax.ShapeDtypeStruct(s, d) for s, d in _layer_specs(*shape)]
    text = str(jax.make_jaxpr(pa._build_layer_call(*cfg))(*specs))
    assert hashlib.sha256(text.encode()).hexdigest() == MISTRAL_BODY[shape]
    off = str(jax.make_jaxpr(pa._build_layer_call(*cfg, False, 0))(*specs))
    assert off == text


# evabyte-6.5b-serve.docs-closed24's steps (EvaByte's widths: 32 KV heads,
# group 1, feed-forward 11008; 4,096 pages; tables of 128 and 256 pages):
# 24 decode rows in the 32 bucket, and beside a prompt chunk of up to 480
# bytes (rows padded to 512 tokens) at a small and the largest bucket
@pytest.mark.parametrize("n_pad,b_pad,t_pad,mp", [
    (32, 32, 1, 128), (32, 32, 1, 256), (64, 32, 512, 128),
    (512, 32, 512, 256),
])
def test_eva_layer_program(one_chip, monkeypatch, n_pad, b_pad, t_pad, mp):
    """The layer program with both switches on at EvaByte's widths: the
    kernel's landing buffers are four times the Mistral block (32 KV
    heads a page) and fit; the unit-offset norms, the K/V scatter, the
    summary epilogue (gather the filled pages, pool, scatter the rows)
    and ONE ragged kernel call are one program whose pools come back in
    the buffers they came in: four in-place page writes, no pool copied."""
    import re

    import paddle_tpu.ops.kernels as kernels
    from paddle_tpu.ops.kernels.paged_attention import _build_layer_call

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    kvh, ffn, npages = 32, 11008, 4096
    n_sum = n_pad // PAGE + b_pad
    run = _build_layer_call(n_pad, E, H, kvh, D, npages, PAGE, b_pad, t_pad,
                            mp, D ** -0.5, 0, False, 1e-5, False, True, n_sum)
    i32, pool = jnp.int32, ((npages, PAGE, kvh, D), BF16)
    specs = [pool, pool, ((n_pad, E), BF16), ((E,), BF16),
             ((E, H * D), BF16), ((E, kvh * D), BF16), ((E, kvh * D), BF16),
             ((H * D, E), BF16), ((E,), BF16), ((E, ffn), BF16),
             ((E, ffn), BF16), ((ffn, E), BF16),
             ((32768, D), jnp.float32), ((32768, D), jnp.float32),
             ((5, n_pad), i32), ((b_pad, t_pad), i32), ((2, n_pad), i32),
             ((b_pad, mp + 2), i32), ((kvh, D), BF16), ((kvh, D), BF16),
             ((3, n_sum), i32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    # decode rows alone: every row takes the kernel's few-row form, and
    # the kernel holds no head-major (kv heads, block, head_dim) copy of
    # a block's pages; beside a prompt chunk it holds one for K and V
    (call,) = _pallas_calls(jax.make_jaxpr(run)(*args).jaxpr,
                            "ragged_paged_attention")
    scratch = [tuple(v.aval.shape) for v in call.params["jaxpr"].invars]
    assert scratch.count((kvh, 16 * PAGE, D)) == (0 if t_pad == 1 else 2)
    text = jax.jit(run, donate_argnums=(0, 1)).lower(
        *args).compile().as_text()
    head = text.splitlines()[0]
    assert re.search(r"\{1\}: \(0, \{\}", head), head[:300]
    assert re.search(r"\{2\}: \(1, \{\}", head), head[:300]
    pools = [ln for ln in text.splitlines()
             if re.search(r"= bf16\[%d,%d,%d,%d\]" % (npages, PAGE, kvh, D),
                          ln) and " parameter(" not in ln]
    assert pools
    for line in pools:
        op = re.search(r"\} (\w[\w-]*)\(", line).group(1)
        assert op in ("scatter", "fusion"), line[:160]
    assert text.count("tpu_custom_call") == 3    # two norms, one attend
    assert len(re.findall(r"ragged_paged_attention/pallas_call",
                          text)) == 1


# sdar-30b-a3b-serve.blocks-closed64's steps (SDAR-30B-A3B's widths:
# hidden 2048, 32 query / 4 KV heads of 128, 128 experts of 768 top-8;
# 5,120 pages; tables of 64 pages, before PR 37 of 32 as well): 64 rows of
# one block of 4 tokens in the 256 bucket, and beside a 256-token prompt
# chunk in the 512; since PR 37 rows of two blocks (a finished block
# behind the open one) in the 256 and 512 buckets, and up to 512 such
# tokens beside the chunk in the 1,024 the largest bucket rounds up to
@pytest.mark.parametrize("n_pad,b_pad,t_pad,mp", [
    (256, 64, 4, 32), (256, 64, 4, 64), (256, 64, 256, 64),
    (512, 64, 256, 64), (256, 64, 8, 64), (512, 64, 8, 64),
    (1024, 64, 256, 64),
])
def test_sdar_layer_program(one_chip, monkeypatch, n_pad, b_pad, t_pad, mp):
    """The layer program with its three other switches on at SDAR's
    widths: the kernel under the block-causal mask at group 8 with rows
    of 4 tokens (32 query rows a KV head: one tile), the per-head q/k
    norms, and the routed feed-forward as the program's second body (the
    router in float32, the sort by expert, three grouped matmuls over the
    [128, 2048, 768] stacks, the combine) with the per-expert counts as a
    fourth result. Both pools come back in the buffers they came in."""
    import re

    import paddle_tpu.ops.kernels as kernels
    from paddle_tpu.ops.kernels.paged_attention import _build_layer_call

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    e, kvh, ne, f, npages = 2048, 4, 128, 768, 5120
    run = _build_layer_call(
        n_pad, e, H, kvh, D, npages, PAGE, b_pad, t_pad, mp, D ** -0.5, 0,
        False, 1e-6, False, False, 0, 4, True, (8, True, "softmax"))
    i32, pool = jnp.int32, ((npages, PAGE, kvh, D), BF16)
    specs = [pool, pool, ((n_pad, e), BF16), ((e,), BF16),
             ((e, H * D), BF16), ((e, kvh * D), BF16), ((e, kvh * D), BF16),
             ((H * D, e), BF16), ((e,), BF16), ((ne, e, f), BF16),
             ((ne, e, f), BF16), ((ne, f, e), BF16),
             ((1024, D), jnp.float32), ((1024, D), jnp.float32),
             ((5, n_pad), i32), ((b_pad, t_pad), i32), ((2, n_pad), i32),
             ((b_pad, mp + 2), i32), ((D,), BF16), ((D,), BF16),
             ((e, ne), BF16)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(run, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    head = text.splitlines()[0]
    assert re.search(r"\{1\}: \(0, \{\}", head), head[:300]
    assert re.search(r"\{2\}: \(1, \{\}", head), head[:300]
    assert len(re.findall(r"ragged_paged_attention/pallas_call",
                          text)) == 1
    # no expert stack is copied or cast whole: the grouped matmuls read
    # the operands where they are
    stacks = [ln for ln in text.splitlines()
              if re.search(r"= \w+\[%d,(%d,%d|%d,%d)\]" % (ne, e, f, f, e),
                           ln) and " parameter(" not in ln]
    assert stacks == [], stacks[:3]
    # the three grouped matmuls are the Pallas kernel, not XLA's
    assert text.count("grouped_matmul/pallas_call") == 3
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


def test_sdar_choose_program(one_chip, monkeypatch):
    """The device-side choice at SDAR's widths: 64 rows x 4 positions
    through the final norm and the 151,936-wide head in float32, the MASK
    id's logit left out, (token, its logit, logsumexp) a position."""
    import types

    import paddle_tpu.ops.kernels as kernels
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    norm = types.SimpleNamespace(_epsilon=1e-6)
    ad = types.SimpleNamespace(
        _programs=None, block_length=4, mask_token_id=151669,
        cfg=types.SimpleNamespace(),
        model=types.SimpleNamespace(
            lm_head=object(), model=types.SimpleNamespace(norm=norm)),
        _unit_offset=lambda n: False)
    choose = PagedLlamaAdapter._step_programs(ad)[3]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((512, 2048), BF16), ((256,), jnp.int32), ((2048,), BF16),
        ((2048, 151936), BF16))]
    compiled = choose.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29


@pytest.mark.parametrize("b,t,max_pages", [
    (64, 1, 256), (64, 64, 256), (64, 16, 64), (8, 1, 1),
])
def test_latent_ragged_step(one_chip, b, t, max_pages):
    """The latent pool's packed step at Xing4.0-29B-A4B's widths (32
    query heads, a cached row of 512 + 64, 12,288 pages of 16): the page
    write, the right-align gather and the absorbed ragged kernel as one
    program. At 64 tokens a row the kernel's blocks and float32 state take
    about 15 MB of VMEM: the call raises its own limit."""
    from paddle_tpu.ops.kernels.paged_attention import _jitted_latent_step

    nh, d, dv, npages, n_pad = 32, 576, 512, 12288, 128
    cfg = (b, t, nh, d, dv, npages, PAGE, max_pages, 0.1, False)
    run = _jitted_latent_step.__wrapped__(cfg, False)
    i32 = jnp.int32
    specs = [((n_pad, nh, d), BF16), ((n_pad, d), BF16), ((n_pad,), i32),
             ((n_pad,), i32), ((b, t), i32), ((npages, PAGE, d), BF16),
             ((b, max_pages), i32), ((b,), i32), ((b,), i32)]
    assert "tpu_custom_call" in _compile(run, one_chip, *specs)


def test_routed_experts_program(one_chip):
    """Router, sort, three grouped matmuls over 64 stacked experts and the
    combine with the shared expert, 256 assignments: one program with the
    group sizes as device data (no host value in it)."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        dropless_moe

    c, f, e, n = 3584, 1024, 64, 64

    def run(x, wr, bias, wg, wu, wd, sg, su, sd, valid):
        return dropless_moe(x, wr, bias, wg, wu, wd, (sg, su, sd), 4, 2.0,
                            True, valid)

    specs = [((n, c), BF16), ((c, e), BF16), ((e,), BF16),
             ((e, c, f), BF16), ((e, c, f), BF16), ((e, f, c), BF16),
             ((c, f), BF16), ((c, f), BF16), ((f, c), BF16),
             ((n,), jnp.bool_)]
    _compile(run, one_chip, *specs)


# the routed experts' stacks and assignments a call: SDAR's [128, 2048,
# 768] and [128, 768, 2048] at 2,048 / 4,096 / 8,192 (the 256, 512 and
# 1,024 packed widths x top-8), Xing4's [64, 3584, 1024] and [64, 1024,
# 3584] at 256 (64 decode rows x top-4)
@pytest.mark.parametrize("m,k,n,e", [
    (m, k, n, 128) for m in (2048, 4096, 8192)
    for k, n in ((2048, 768), (768, 2048))
] + [(256, 3584, 1024, 64), (256, 1024, 3584, 64)])
def test_grouped_matmul(one_chip, m, k, n, e):
    """The kernel at the tiling the code chooses for the shapes: its
    double-buffered tiles fit the default scoped VMEM, which interpret
    mode never checks."""
    from paddle_tpu.ops.kernels.grouped_matmul import gmm_pallas

    text = _compile(gmm_pallas, one_chip, ((m, k), BF16), ((e, k, n), BF16),
                    ((e,), jnp.int32))
    assert "grouped_matmul" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_fwd_s4096(one_chip, window):
    from paddle_tpu.ops.kernels.flash_attention import _flash_fwd_pallas

    f = functools.partial(_flash_fwd_pallas, causal=True,
                          scale=D ** -0.5, block_q=512, block_k=512,
                          interpret=False, window=window)
    specs = [((H, 4096, D), BF16), ((KVH, 4096, D), BF16),
             ((KVH, 4096, D), BF16)]
    assert "tpu_custom_call" in _compile(f, one_chip, *specs)


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_bwd_s4096(one_chip, window):
    from paddle_tpu.ops.kernels.flash_attention import _flash_bwd_pallas

    f = functools.partial(_flash_bwd_pallas, causal=True,
                          scale=D ** -0.5, block_q=512, block_k=512,
                          interpret=False, window=window)
    specs = [((H, 4096, D), BF16), ((KVH, 4096, D), BF16),
             ((KVH, 4096, D), BF16), ((H, 4096, D), BF16),
             ((H, 4096), jnp.float32), ((H, 4096, D), BF16)]
    assert "tpu_custom_call" in _compile(f, one_chip, *specs)


def test_flash_varlen_fwd(one_chip):
    from paddle_tpu.ops.kernels.flash_varlen import _varlen_fwd_pallas

    f = functools.partial(_varlen_fwd_pallas, causal=True,
                          scale=D ** -0.5, block_q=512, block_k=512,
                          interpret=False)
    t = 4096
    specs = [((H, t, D), BF16), ((KVH, t, D), BF16), ((KVH, t, D), BF16)]
    specs += [((t,), jnp.int32)] * 4
    assert "tpu_custom_call" in _compile(f, one_chip, *specs)


@pytest.mark.parametrize("rows", [3, 8, 13, 260, 4096])
def test_rms_norm_rows(one_chip, rows):
    """Rows that are not a multiple of 8 used to fall to one-row
    blocks, which the TPU lowering refuses (dense generate() at batch
    2-7, odd packed lengths)."""
    from paddle_tpu.ops.kernels.rms_norm import _rms_pallas

    f = functools.partial(_rms_pallas, eps=1e-5, interpret=False)
    text = _compile(f, one_chip, ((rows, E), BF16), ((E,), BF16))
    assert "tpu_custom_call" in text


def test_rope(one_chip):
    from paddle_tpu.ops.kernels.rope import apply_rotary_emb

    def f(x, cos, sin, pos):
        return apply_rotary_emb(x, cos, sin, position_ids=pos)

    _compile(f, one_chip, ((1, 128, H, D), BF16),
             ((32768, D), jnp.float32), ((32768, D), jnp.float32),
             ((1, 128), jnp.int32))


def test_kernels_under_mp4_mesh(topo, monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: under a
    4-device mesh rms_norm and flash attention (forward and backward)
    must reach the compiler inside a shard_map (heads over mp). The CPU
    backend is steered to the chip's dispatch here, in the test."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu.ops.kernels as K
    from paddle_tpu.distributed import mesh as M

    monkeypatch.setattr(K, "on_tpu", lambda: True)
    mesh = M.build_global_mesh(("dp", "mp"), (1, 4),
                               devices=np.array(topo.devices))
    try:
        def loss(x, w, q, k, v):
            y = K.rms_norm(x, w).astype(jnp.float32).sum()
            o = K.flash_attention(q, k, v, causal=True)
            return y + o.astype(jnp.float32).sum()

        def spec(shape, *names):
            return jax.ShapeDtypeStruct(
                shape, BF16, sharding=NamedSharding(mesh, P(*names)))

        heads = (None, None, "mp", None)
        text = jax.jit(jax.grad(loss, argnums=(0, 2, 3))).lower(
            spec((1, 2048, E)), spec((E,)),
            spec((1, 2048, H, D), *heads), spec((1, 2048, KVH, D), *heads),
            spec((1, 2048, KVH, D), *heads)).compile().as_text()
    finally:
        M.reset_mesh()
    assert text.count("tpu_custom_call") >= 3


def test_grouped_matmul_under_mp4_mesh(topo, monkeypatch):
    """The routed experts' kernel under a 4-device mesh, forward and
    backward (``ragged_dot``'s): every device runs the whole call on
    replicated operands inside a shard_map, whatever the operands'
    sharding."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu.ops.kernels as K
    from paddle_tpu.distributed import mesh as M
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        dropless_moe

    monkeypatch.setattr(K, "on_tpu", lambda: True)
    mesh = M.build_global_mesh(("dp", "mp"), (1, 4),
                               devices=np.array(topo.devices))
    try:
        def loss(x, wr, wg, wu, wd):
            y, _ = dropless_moe(x, wr, None, wg, wu, wd, None, 2,
                                scoring="softmax")
            return y.astype(jnp.float32).sum()

        def spec(shape, *names):
            return jax.ShapeDtypeStruct(
                shape, BF16, sharding=NamedSharding(mesh, P(*names)))

        c, f, e = 512, 256, 8
        text = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
            spec((64, c), "mp"), spec((c, e)), spec((e, c, f)),
            spec((e, c, f), None, None, "mp"), spec((e, f, c))
        ).compile().as_text()
    finally:
        M.reset_mesh()
    assert text.count("grouped_matmul/pallas_call") == 3
