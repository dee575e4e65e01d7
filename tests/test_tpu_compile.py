"""Mosaic compiles of every Pallas entry point for a *described* TPU v5e.

Interpret mode (the rest of the kernel tests) cannot see what the chip's
compiler refuses: a block that breaks the (8, 128)-or-whole-array rule,
a tile set over the 16 MB of scoped VMEM, a kernel GSPMD is asked to
partition. libtpu is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so each kernel
is lowered with ``interpret=False`` at Pythia-1.4B shapes (hidden 2048,
ffn 8192, 16 heads × 128, vocab 50304) and compiled — nothing runs, so
these say nothing about results or times. ``chip_smoke.py`` checks the
numbers on the chip.

Skipped only where the topology cannot be described. The persistent
compile cache is off around them: an entry written for an unattached chip
cannot be read back and warns on every later run.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P
from tpu_compile_harness import (_no_persistent_cache, nbytes,  # noqa: F401
                                 spec_on, v5e)

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import pallas_utils
from deepspeed_tpu.ops import quantizer as qz

FP8 = jnp.float8_e4m3fn


def _compile(fn, *shapes, sharding):
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    return text


# ------------------------------------------------------------------- flash

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("block", [512, 1024])
def test_flash_fwd_bwd(v5e, dtype, block):
    qkv = ((8, 2048, 16, 128), dtype)

    def loss(q, k, v):
        o_hm, lse = fa._fwd_pallas(q, k, v, True, block, block, 0,
                                   interpret=False)
        g = o_hm.transpose(0, 2, 1, 3)
        return fa._bwd_pallas(q, k, v, o_hm, lse, g, True, block, block, 0,
                              interpret=False)

    text = _compile(loss, qkv, qkv, qkv, sharding=SingleDeviceSharding(v5e[0]))
    assert text.count("tpu_custom_call") >= 3        # fwd, dq, dkv


@pytest.mark.parametrize("mesh_axes", [{"fsdp": 4}, {"tensor": 4},
                                       {"data": 2, "tensor": 2}])
def test_flash_call_on_a_four_device_mesh(v5e, mesh_axes, monkeypatch):
    """The model's own flash call (``_local_attention``), operands sharded
    over a four-chip mesh, forward and grad — under plain jit this is
    "Mosaic kernels cannot be automatically partitioned"."""
    from deepspeed_tpu.models import transformer as tr
    from deepspeed_tpu.parallel import topology as topo

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    t = topo.MeshTopology.build(devices=v5e, **{"data": 1, **mesh_axes})
    topo.set_topology(t)
    sh = NamedSharding(t.mesh, P(topo.BATCH_AXES, None, "tensor", None))
    qkv = ((8, 2048, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        o = tr._local_attention(q, k, v, tr.PYTHIA_1B4)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
                    sharding=sh)
    # each device runs the kernel on its own batch/head shard: no operand
    # is gathered to get there
    assert "all-gather" not in text and "all-to-all" not in text


# ------------------------------------------------------------------- paged

@pytest.mark.parametrize("pool", [jnp.bfloat16, jnp.float32, jnp.int8, FP8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("chunk", [1, 8, 256])
def test_paged_attention(v5e, pool, chunk):
    """The kernel as the serving forward calls it: the whole stacked pool
    [L, NB, KH, bs, D] and a traced layer scalar."""
    L, N, H, KH, D, bs, MB, NB = 4, 8, 16, 16, 128, 64, 32, 512
    quant = pool in (jnp.int8, FP8)
    qdt = jnp.float32 if pool == jnp.float32 else jnp.bfloat16
    shapes = [((N, chunk, H, D), qdt), ((L, NB, KH, bs, D), pool),
              ((L, NB, KH, bs, D), pool), ((), jnp.int32),
              ((N, MB), jnp.int32), ((N,), jnp.int32), ((N,), jnp.int32)]
    if quant:
        shapes += [((L, NB, KH), jnp.float32)] * 2   # the scale planes

    def attend(q, k, v, layer, tbl, sp, nt, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return pa._paged_pallas(q, k, v, tbl, sp, nt, layer=layer,
                                interpret=False, **kw)

    _compile(attend, *shapes, sharding=SingleDeviceSharding(v5e[0]))


@pytest.mark.parametrize("geometry", [
    # N, C, H, KH, D, MB, NB, window: the cells' other shapes — Mistral's
    # GQA decode and chunk steps, Qwen3-Next's D = 256 / KH = 2 pool under
    # a 512-slot table with its 2,048-row pieces, a TP shard's two heads
    (32, 1, 32, 8, 128, 64, 1152, 4096), (32, 256, 32, 8, 128, 64, 1152, 4096),
    (8, 1, 16, 2, 256, 512, 4096, 0), (1, 256, 16, 2, 256, 512, 4096, 0),
    (32, 1, 8, 2, 128, 64, 1152, 4096),
    # a chunk row as a forward of its own (what the batch cell's prefill
    # runs at); ``forward_verify`` still reaches [32, 256]
    (1, 256, 32, 8, 128, 64, 1152, 4096),
], ids=["mistral_decode", "mistral_chunk", "qwen3_next_decode",
        "qwen3_next_piece", "mistral_decode_tp4", "mistral_chunk_alone"])
def test_paged_attention_at_the_cells_geometries(v5e, geometry, monkeypatch):
    """The tiles ``_tiles`` picks for each (heads a step, blocks a turn)
    fit the chip's scoped VMEM: the estimate in ``_step_bytes`` is held to
    what the compiler allocates — the kernel is compiled with that
    estimate as its whole allowance (``vmem_limit_bytes``, set here and
    not in the program), so buffers that grew past it are refused."""
    N, C, H, KH, D, MB, NB, window = geometry
    rows = (H // KH) * C
    assert rows <= pa.MAX_QUERY_ROWS
    bf16 = jnp.bfloat16
    kh_t, T = pa._tiles(rows, D, KH, 64, MB, bf16, bf16)
    estimate = pa._step_bytes(kh_t, T, rows, D, 64, bf16, bf16)
    assert estimate <= pa.VMEM_BUDGET
    monkeypatch.setattr(pa.pltpu, "CompilerParams", functools.partial(
        pa.pltpu.CompilerParams, vmem_limit_bytes=estimate))
    shapes = [((N, C, H, D), bf16), ((2, NB, KH, 64, D), bf16),
              ((2, NB, KH, 64, D), bf16), ((), jnp.int32),
              ((N, MB), jnp.int32), ((N,), jnp.int32), ((N,), jnp.int32)]

    def attend(q, k, v, layer, tbl, sp, nt):
        return pa._paged_pallas(q, k, v, tbl, sp, nt, layer=layer,
                                window=window, interpret=False)

    _compile(attend, *shapes, sharding=SingleDeviceSharding(v5e[0]))


@pytest.fixture(scope="module")
def paged_forward(v5e, _no_persistent_cache):
    """``PagedCausalLM.<entry>`` at ``widths`` (``_dense_widths``)
    compiled for one described chip at the bucket ``[N, C]`` over the
    benchmark's 336 blocks of 64 tokens -- with ``rows``, the merged
    layout: ``bucket`` is the tokens' ``[1, C + rows]`` -- on the serving
    layout of the parameters (``fuse_qkv``) or on the model's three
    leaves: the executable, the parameters' shapes and the pool's. A
    program that several tests read is compiled once."""
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM, fuse_qkv
    from deepspeed_tpu.models import transformer as tr

    spec = spec_on(v5e[0])
    programs = {}

    def build(widths, bucket, pool, entry, fused, rows):
        cfg, _ = _dense_widths(widths)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pa, "_on_tpu", lambda: True)
            patch.setattr(modules, "on_tpu", lambda: True)
            model = tr.CausalLM(cfg)
            bs, NB, MB = 64, 336, 32
            paged = PagedCausalLM(model, bs, MB)
            init = (lambda key: fuse_qkv(model.init(key))) if fused \
                else model.init
            shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
            params = jax.tree.map(lambda a: spec(a.shape, jnp.bfloat16),
                                  shapes)
            shape = (cfg.num_layers, NB, cfg.kv_heads, bs, cfg.head_dim)
            cache = {"k": spec(shape, pool), "v": spec(shape, pool)}
            if pool == jnp.int8:
                cache["k_scale"] = spec(shape[:3], jnp.float32)
                cache["v_scale"] = spec(shape[:3], jnp.float32)
            N = rows or bucket[0]
            kw = {"verify_width": 4} if entry == "forward_verify" else {}
            compiled = getattr(paged, entry).lower(
                params, cache, spec(bucket, jnp.int32), spec((N,), jnp.int32),
                spec((N,), jnp.int32), spec((N, MB), jnp.int32),
                **kw).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled, shapes, cache

    def compiled(widths, bucket, *, pool=jnp.bfloat16, entry="forward",
                 fused=True, rows=None):
        key = (widths, bucket, jnp.dtype(pool).name, entry, fused, rows)
        if key not in programs:
            programs[key] = build(widths, bucket, pool, entry, fused, rows)
        return programs[key]

    return compiled


@pytest.mark.parametrize("bucket", [(1, 1), (16, 1), (8, 256)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("pool", [jnp.bfloat16, jnp.int8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("entry", ["forward", "forward_verify"])
def test_paged_forward_keeps_the_pool_in_place(paged_forward, entry, pool,
                                               bucket):
    """The serving forward at Pythia-1.4B widths (6 of its 24 layers, the
    benchmark's 336 blocks of 64 tokens, the parameters in the serving
    layout): the chip's compiler aliases every pool leaf to the output and
    keeps its temporaries under ONE layer's slab and the bucket's own q, k
    and v. This is the reading that decides how the write is formulated:
    XLA ran a per-token scatter ``pool.at[layer, blk, :, slot, :]`` behind
    two transposing copies of the whole pool (temporaries of one pool leaf
    at [16, 1]), which the CPU backend's compile does not show; the
    whole-block scatter of ``kv_write.py`` is the pool's own layout."""
    cfg, _ = _dense_widths("pythia")
    compiled, _, cache = paged_forward("pythia", bucket, pool=pool,
                                       entry=entry)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes(s) for s in cache.values())
    slab = nbytes(cache["k"]) // cfg.num_layers
    # the one activation that is wider on the serving layout: the fused
    # projection's result lives beside its three cuts (25 MB at [8, 256],
    # where an int8 slab is 44 MB)
    N, C = bucket
    qkv = N * C * (cfg.num_heads + 2 * cfg.kv_heads) * cfg.head_dim * 2
    assert mem.temp_size_in_bytes < slab + qkv, (
        f"{mem.temp_size_in_bytes} B of temporaries against a layer's slab "
        f"of {slab} B and {qkv} B of q, k and v: the pool is being copied")


def _computations(text):
    """The computations of an optimized HLO module, name -> lines."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _layer_bodies(comps):
    """The names of the layer loop's body computations, of
    ``_computations``."""
    bodies = {m.group(1) for lines in comps.values()
              for line in lines if "layers/while" in line
              for m in [re.search(r"\bwhile\(.*body=%?([\w.\-]+)", line)]
              if m}
    assert bodies, "the program has no layer loop"
    return bodies


def _staged_weights(text, weight_shapes):
    """The instructions of the layer loop's body (optimized HLO ``text``)
    whose result is a whole weight matrix -- dims in ``weight_shapes`` --
    and that are no matmul: a bare ``dynamic-slice``, a fusion that holds
    no ``convolution`` / ``dot``, a ``copy``."""
    comps = _computations(text)
    staged = []
    for body in _layer_bodies(comps):
        for line in comps[body]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                         r"([\w\-]+)\(", line)
            if not m or tuple(map(int, m.group(2).split(","))) \
                    not in weight_shapes:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            inner = "\n".join(comps.get(called.group(1), ())) if called \
                else ""
            if not re.search(r"\b(convolution|dot)\(", inner):
                staged.append(f"{m.group(3)} {m.group(1)} [{m.group(2)}]")
    return staged


def _dense_widths(widths):
    """Pythia-1.4B's widths at 6 layers or Mistral-7B's (4096 / 14336,
    32 / 8 heads) at 4, in bfloat16, and the function that gives a
    parameter tree's weight-matrix shapes as one layer's slice has them."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tr

    base, layers = {"pythia": (tr.PYTHIA_1B4, 6),
                    "mistral": (tr.MISTRAL_7B, 4)}[widths]
    return (dataclasses.replace(base, num_layers=layers, dtype=jnp.bfloat16),
            lambda tree: {(1,) + leaf.shape[1:]
                          for leaf in jax.tree.leaves(tree["layers"])
                          if leaf.ndim == 3})


@pytest.mark.parametrize("widths,bucket", [
    ("pythia", (1, 1)), ("pythia", (2, 1)), ("pythia", (16, 1)),
    ("pythia", (1, 256)), ("mistral", (32, 1)), ("mistral", (1, 256))],
    ids=lambda v: v if isinstance(v, str) else f"{v[0]}x{v[1]}")
def test_paged_forward_multiplies_weights_where_they_lie(paged_forward,
                                                         widths, bucket):
    """The dense serving forward on the serving layout of its parameters
    (``fuse_qkv``: one ``wqkv`` leaf) at Pythia-1.4B's widths (6 layers)
    and Mistral-7B's (4096 / 14336, 32 / 8 heads, 4 layers): the layer
    loop's body holds no ``copy`` and no stand-alone ``dynamic-slice``
    fusion whose result is a weight matrix ([1, in, out] of any stacked
    leaf) -- every weight is sliced inside the fusion that multiplies by
    it, streamed from HBM once.

    On three leaves (the parent of PR 37) this fails at every bucket wider
    than one row: ``wq`` / ``wk`` / ``wv`` are each sliced into a buffer of
    their own (``constant_dynamic-slice_fusion``, memory space ``S(1)``)
    and transposed by a ``copy`` ({2,1,0} -> {1,2,0}) before their matmul --
    three ``bf16[1,2048,2048]`` at Pythia's widths, ``bf16[1,4096,4096]``
    and two ``bf16[1,4096,1024]`` at Mistral's, 14% of a decode forward on
    the chip -- while ``wo`` and the MLP's matrices never were. ``[1, 1]``
    had no copy on three leaves either: its case holds the one-row
    program to that."""
    cfg, matrices = _dense_widths(widths)
    compiled, served, _ = paged_forward(widths, bucket)
    assert "wqkv" in served["layers"] and "wq" not in served["layers"]
    assert (1, cfg.hidden_size, (cfg.num_heads + 2 * cfg.kv_heads)
            * cfg.head_dim) in matrices(served)
    assert _staged_weights(compiled.as_text(), matrices(served)) == []


@pytest.mark.parametrize("widths,rows", [("mistral", 32), ("pythia", 2)])
def test_the_merged_forward_streams_each_weight_once(paged_forward, widths,
                                                     rows):
    """The merged program at Mistral-7B's ``[1, 256 + 32]`` and
    Pythia-1.4B's ``[1, 256 + 2]``: what it is for, read from the
    optimized HLO. The layer loop's body multiplies each of ``wqkv``,
    ``wo`` and the MLP's matrices once (one pass over each weight for both
    parts), stages none of them, calls ``paged_attention`` twice (the
    chunk as ``[1, 256]``, the rows as ``[S, 1]``), and every pool leaf is
    aliased to the output with temporaries under one layer's slab: no copy
    of the pool between the two parts' writes."""
    cfg, matrices = _dense_widths(widths)
    compiled, served, cache = paged_forward(widths, (1, 256 + rows),
                                            rows=rows)
    text = compiled.as_text()
    assert _staged_weights(text, matrices(served)) == []
    comps = _computations(text)
    (body,) = _layer_bodies(comps)
    body = comps[body]
    # the stacked leaves as the body names them, by their dims
    stacked = {m.group(1): (1,) + tuple(map(int, m.group(2).split(",")))[1:]
               for line in body for m in [re.match(
                   r"\s*%?([\w.\-]+) = \w+\[([\d,]+)\]\S* get-tuple-element\(",
                   line)] if m}
    per_weight = dict.fromkeys(matrices(served), 0)
    for line in body:
        called = re.search(r"fusion\((.*?)\), kind=.*calls=%?([\w.\-]+)", line)
        if not called or not re.search(
                r"\b(convolution|dot)\(", "\n".join(comps[called.group(2)])):
            continue        # no matmul
        for name in re.findall(r"%([\w.\-]+)", called.group(1)):
            if stacked.get(name) in per_weight:
                per_weight[stacked[name]] += 1
    mlp = (1, cfg.hidden_size, cfg.intermediate_size)
    gated = 2 if widths == "mistral" else 1         # SwiGLU: gate and up
    assert per_weight.pop(mlp) == gated
    assert set(per_weight.values()) == {1}, per_weight
    calls = [line for line in body if "custom-call(" in line
             and "paged_attention" in line]
    assert len(calls) == 2
    assert sorted(re.search(r"= \w+\[([\d,]+)\]", c).group(1)
                  for c in calls) == sorted(
        [f"1,{cfg.kv_heads},{256 * cfg.num_heads // cfg.kv_heads},128",
         f"{rows},{cfg.kv_heads},{cfg.num_heads // cfg.kv_heads},128"])
    mem = compiled.memory_analysis()
    pool = sum(nbytes(s) for s in cache.values())
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < pool // (2 * cfg.num_layers)


@pytest.mark.parametrize("widths,bucket", [("pythia", (2, 1)),
                                           ("mistral", (32, 1))],
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v[0]}x{v[1]}")
def test_three_leaves_are_staged_and_transposed(paged_forward, widths,
                                                bucket):
    """What the serving layout is for, and that ``_staged_weights`` sees
    it: on the model's three leaves (the body quantized trees and TP
    shards still run) the loop's body stages ``wq``, ``wk`` and ``wv`` --
    a stand-alone slice and a ``copy`` each -- and no other matrix. If
    this fails because nothing is staged, the compiler has learnt to
    slice them in place and ``fuse_qkv`` has lost its reason."""
    cfg, matrices = _dense_widths(widths)
    compiled, shapes, _ = paged_forward(widths, bucket, fused=False)
    qkv = sorted(",".join(map(str, (1,) + shapes["layers"][n].shape[1:]))
                 for n in ("wq", "wk", "wv"))
    staged = _staged_weights(compiled.as_text(), matrices(shapes))
    assert sorted(s.split("[")[1][:-1] for s in staged
                  if s.startswith("copy ")) == qkv
    assert sorted(s.split("[")[1][:-1] for s in staged
                  if s.startswith("fusion ")) == qkv
    assert len(staged) == 6


def test_the_kernels_carry_their_names(v5e):
    """``name=`` on each pallas_call: the custom call's HLO instruction and
    its op_name take it, so a device trace tells the kernels apart by name
    (``%paged_attention.1``, not ``%closed_call.1``)."""
    one = SingleDeviceSharding(v5e[0])
    N, H, D, bs, MB, NB = 8, 16, 128, 64, 32, 512
    kv = ((NB, H, bs, D), jnp.bfloat16)      # a one-layer pool

    def attend(q, k, v, tbl, sp, nt):
        with jax.named_scope("attend"):
            return pa._paged_pallas(q, k, v, tbl, sp, nt, interpret=False)

    text = _compile(attend, ((N, 1, H, D), jnp.bfloat16), kv, kv,
                    ((N, MB), jnp.int32), ((N,), jnp.int32),
                    ((N,), jnp.int32), sharding=one)
    assert re.search(r"%paged_attention(\.\d+)? = .* custom-call\(", text)
    assert 'op_name="jit(attend)/attend/paged_attention/' in text

    qkv = ((2, 1024, 16, 128), jnp.bfloat16)

    def flash(q, k, v):
        o_hm, lse = fa._fwd_pallas(q, k, v, True, 512, 512, 0,
                                   interpret=False)
        return fa._bwd_pallas(q, k, v, o_hm, lse, o_hm.transpose(0, 2, 1, 3),
                              True, 512, 512, 0, interpret=False)

    text = _compile(flash, qkv, qkv, qkv, sharding=one)
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(", text), name


# --------------------------------------------------------------- quantizer

_PROJ = [(2048, 8192), (8192, 2048), (2048, 2048), (2048, 50304)]


@pytest.fixture
def qz_on_tpu(monkeypatch):
    # the quantizer's kernels take interpret from on_tpu(); the CPU backend
    # answers no, so the test steers it — not an option of the program
    monkeypatch.setattr(qz, "_on_tpu", lambda: True)


@pytest.mark.parametrize("payload", [jnp.int8, FP8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("k,n", _PROJ)
@pytest.mark.parametrize("m", [8, 256, 2048])
def test_quantized_matmul(v5e, qz_on_tpu, m, k, n, payload):
    _compile(lambda x, q, s: qz._qmm_pallas(x, q, s, 128, jnp.bfloat16),
             ((m, k), jnp.bfloat16), ((k, n), payload),
             ((k, n // 128), jnp.float32),
             sharding=SingleDeviceSharding(v5e[0]))


# stacked [L·in, out] is what the weight-quant build and ZeRO++ really pass
@pytest.mark.parametrize("rows,n", _PROJ + [(24 * 2048, 8192)])
def test_block_quantize_dequantize(v5e, qz_on_tpu, rows, n):
    one = SingleDeviceSharding(v5e[0])
    _compile(lambda x: qz._quantize_pallas(x, 8, 128),
             ((rows, n), jnp.float32), sharding=one)
    _compile(lambda q, s: qz._dequantize_pallas(q, s, 128, jnp.bfloat16),
             ((rows, n), jnp.int8), ((rows, n // 128), jnp.float32),
             sharding=one)


def test_untileable_shapes_are_decided_before_the_call(qz_on_tpu):
    """Which formulation runs is a rule on the shape, read before the
    call — never a compile that failed and was caught: a row too long for
    any VMEM tile, or scale groups that are not lane-aligned."""
    assert qz._pallas_2d_ok(2048, 8192, 128)
    assert not qz._pallas_2d_ok(8, 1 << 21, 128)
    assert qz._qmm_pallas_ok(8, 2048, 8192, 128)
    assert not qz._qmm_pallas_ok(8, 2048, 8192, 64)


# ---------------------------------------------------------------- platform

def test_on_tpu_propagates_a_backend_error(monkeypatch):
    """A backend that cannot initialize is an error, not "not on TPU" —
    that answer flips every kernel to interpret mode or the XLA path."""
    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", dead)
    pallas_utils.on_tpu.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            pallas_utils.on_tpu()
    finally:
        monkeypatch.undo()
        pallas_utils.on_tpu.cache_clear()
    assert pallas_utils.on_tpu() is (jax.default_backend() == "tpu")
