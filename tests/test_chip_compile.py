"""The main path's Pallas kernels, compiled for a DESCRIBED v5e chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (``v5e:2x2``): what it refuses costs no chip
time. Every kernel runs at the widths of the 124M model — 12 heads,
D=64, E=768, 32 slots at L=1024 — since interpret mode (the rest of the
kernel tests) cannot see tiling, VMEM or 64-bit-type refusals. A
compile that passes is not a chip run: ``chip_smoke.py`` is that.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, which skips when
it cannot be; shardings and shapes are built in fixtures and tests,
never at import, in a ``skipif`` or in ``parametrize`` arguments; all
such tests live in this ONE file (only one process may load the TPU
library, and under xdist a file goes to one worker); the persistent
compile cache is off around them.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_kernels as pk

S, L, H, D, E, V = 32, 1024, 12, 64, 768, 32000
BF16, F32, I8, U8, I32 = (jnp.bfloat16, jnp.float32, jnp.int8,
                          jnp.uint8, jnp.int32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture()
def compile_for_chip(one_chip):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        return text
    return run


def _flash(window=0):
    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=False)
    return fwd


def _flash_grad(window=0):
    fwd = _flash(window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    return bwd


@pytest.mark.parametrize("b,t,window", [(8, 1024, 0), (2, 4096, 0),
                                        (8, 1024, 256)])
def test_flash_attention_fwd(compile_for_chip, b, t, window):
    compile_for_chip(_flash(window), *[((b, t, H, D), BF16)] * 3)


@pytest.mark.parametrize("b,t,window", [(8, 1024, 0), (2, 4096, 0),
                                        (8, 1024, 256)])
def test_flash_attention_bwd(compile_for_chip, b, t, window):
    text = compile_for_chip(_flash_grad(window),
                            *[((b, t, H, D), BF16)] * 3)
    assert text.count("tpu_custom_call") >= 3       # fwd, dQ, dK/dV


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from mxnet_tpu.parallel import build_mesh
    return build_mesh({"dp": 2, "tp": 2}, topo.devices)


@pytest.mark.parametrize("fn", ["fwd", "bwd"])
def test_flash_attention_over_dp_x_tp(mesh_2x2, fn):
    """The four-chip trainer's attention: operands sharded batch over
    dp and heads over tp on the described 2x2. Under ``kernel_mesh``
    (as ``ParallelTrainer`` traces its step) the kernel partitions
    itself and compiles; without it the compiler refuses the Mosaic
    call, which the CPU interpreter — it inlines kernels — never
    shows."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh_2x2, P("dp", None, "tp", None))
    args = [jax.ShapeDtypeStruct((8, 1024, H, D), BF16, sharding=sh)] * 3
    f = jax.jit(_flash() if fn == "fwd" else _flash_grad())
    with pk.kernel_mesh(mesh_2x2):
        text = f.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if fn == "fwd" else 3)
    with pytest.raises(Exception, match="cannot be automatically "
                                        "partitioned"):
        jax.jit(_flash() if fn == "fwd" else _flash_grad()) \
            .lower(*args).compile()


@pytest.mark.parametrize("chunk,kv,qdt", [
    (1, H, "bfloat16"),     # plain decode
    (5, H, "bfloat16"),     # speculative verify chunk
    (1, 4, "bfloat16"),     # grouped-query: 3 query heads per kv head
    (1, 3, "bfloat16"),     # a tp=4 shard: 3 local kv heads, 192 lanes
    (1, H, "float32"),
])
def test_paged_attention_float_kv(compile_for_chip, chunk, kv, qdt):
    h = H if kv in (H, 4) else kv
    qdt = jnp.dtype(qdt)
    # the cache as the decoder stores it: [S, L, Hkv*D]
    compile_for_chip(
        lambda q, k, v, p: pk.paged_attention(q, k, v, p, kv_heads=kv,
                                              interpret=False),
        ((S, chunk, h, D), qdt), ((S, L, kv * D), qdt),
        ((S, L, kv * D), qdt), ((S,), I32))


@pytest.mark.parametrize("chunk", [1, 5])
def test_paged_attention_int8_kv(compile_for_chip, chunk):
    compile_for_chip(
        lambda q, k, v, p, ks, vs: pk.paged_attention(
            q, k, v, p, kv_heads=H, k_scale=ks, v_scale=vs,
            interpret=False),
        ((S, chunk, H, D), BF16), ((S, L, H * D), I8), ((S, L, H * D), I8),
        ((S,), I32), ((S, L, H), F32), ((S, L, H), F32))


# (rows, contraction, output channels): the QKV, out, FFN and
# unembedding projections of a decode round (m=32), of one sequence and
# of an odd-length prompt (rows ride whole, unpadded)
_QMM = [(32, E, 3 * E), (32, E, E), (32, E, 4 * E), (32, 4 * E, E),
        (32, E, V), (1, E, 3 * E), (3, E, 3 * E)]


@pytest.mark.parametrize("m,e,f", _QMM)
@pytest.mark.parametrize("xdt", ["bfloat16", "float32"])
def test_quant_matmul_int8(compile_for_chip, m, e, f, xdt):
    compile_for_chip(
        lambda x, q, s: pk.quant_matmul(x, q, s, bits=8,
                                        interpret=False),
        ((m, e), jnp.dtype(xdt)), ((f, e), I8), ((f,), F32))


@pytest.mark.parametrize("m,e,f", _QMM)
@pytest.mark.parametrize("xdt", ["bfloat16", "float32"])
def test_quant_matmul_int4(compile_for_chip, m, e, f, xdt):
    group = 64
    compile_for_chip(
        lambda x, q, s: pk.quant_matmul(x, q, s, bits=4, group=group,
                                        interpret=False),
        ((m, e), jnp.dtype(xdt)), ((f, e // 2), U8),
        ((f, e // group), F32))


def test_quant_matmul_engine_chunks_are_lane_legal(compile_for_chip):
    """The kernel compiles at the chunk the engine passes it
    (``resolve_chunk``, shared with the fori walk) for every
    projection of the model; a chunk the lane axis cannot hold is
    refused before the compiler sees it, never replaced."""
    from mxnet_tpu.serving.quant import resolve_chunk
    for f in (3 * E, E, 4 * E, V):
        chunk = resolve_chunk(f)
        assert chunk in (128, 256) and f % chunk == 0
        compile_for_chip(
            lambda x, q, s: pk.quant_matmul(x, q, s, bits=8,
                                            block_f=chunk,
                                            interpret=False),
            ((32, E), BF16), ((f, E), I8), ((f,), F32))
    with pytest.raises(ValueError, match="multiple of 128"):
        pk.quant_matmul(jnp.zeros((32, E), BF16), jnp.zeros((E, E), I8),
                        jnp.ones((E,), F32), bits=8, block_f=64,
                        interpret=False)


@pytest.mark.parametrize("m", [8192, 32])
def test_fused_linear(compile_for_chip, m):
    compile_for_chip(
        lambda x, w, b: pk.fused_linear(x, w, b, "relu",
                                        interpret=False),
        ((m, E), BF16), ((E, 4 * E), BF16), ((4 * E,), BF16))


def test_matmul_stats(compile_for_chip):
    compile_for_chip(
        lambda x, w: pk.matmul_stats(x, w, interpret=False),
        ((256 * 56 * 56, 64), BF16), ((64, 256), BF16))


def test_fused_conv_bn_act(compile_for_chip):
    compile_for_chip(
        lambda x, w, s, b: pk.fused_conv_bn_act(
            x, w, s, b, stride=(1, 1), pad=(1, 1), interpret=False),
        ((32, 64, 56, 56), BF16), ((64, 64, 3, 3), BF16), ((64,), F32),
        ((64,), F32))


def test_striped_pair_attention(compile_for_chip):
    def fwd(q, k, v, a, b):
        return pk.striped_pair_attention(q, k, v, a, b, n_stride=4,
                                         interpret=False)

    def bwd(q, k, v, a, b):
        return jax.grad(
            lambda q, k, v: sum(x.astype(F32).sum()
                                for x in fwd(q, k, v, a, b)),
            argnums=(0, 1, 2))(q, k, v)

    qkv = [((8 * H, 1024, D), BF16)] * 3 + [((), I32)] * 2
    compile_for_chip(fwd, *qkv)
    compile_for_chip(bwd, *qkv)


def test_rtc_user_kernel(compile_for_chip):
    """A user kernel with plain python ints: traced x64-off like the
    library's own, or Mosaic would see int64."""
    from mxnet_tpu.rtc import Rtc

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2 + 1

    rtc = Rtc("double_plus_one", kernel, [(256, 512)], interpret=False)
    compile_for_chip(rtc.apply, ((256, 512), F32))


@pytest.fixture(scope="module")
def serve_chat_engine():
    """The benchmark's serve-chat engine at OPT-1.3B widths (hidden
    2048, 32 heads of 64, ffn 8192, the whole vocabulary, bf16) cut to
    2 layers: 32 slots x 1024 rows (the cell's since PR 33), 8 steps a
    round, and the
    speculative verify program beside the decode program. Weights are
    zeros: only shapes reach the compiler."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_transformer_lm
    e, h, f, v, layers = 2048, 32, 8192, 50272, 2
    sym = get_transformer_lm(v, num_layers=layers, embed_dim=e,
                             num_heads=h, ffn_hidden=f, impl="flash",
                             pos_encoding="learned")
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.zeros(s, BF16)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    params["pos_embed"] = jnp.zeros((2048, e), BF16)
    dec = mx.parallel.Decoder(sym, params, max_len=1024,
                              compute_dtype="bfloat16",
                              weight_dtype="float")
    return mx.serving.InferenceEngine(
        dec, slots=32, prefill_buckets=(512, 768), steps_per_round=8,
        prefix_cache_mb=0, prefill_chunk=0, spec_k=4, draft="ngram")


@pytest.mark.parametrize("program", ["decode", "verify"])
def test_decode_program_holds_no_copy_of_the_cache(serve_chat_engine,
                                                   one_chip, program,
                                                   monkeypatch):
    """The stored KV layout is the layout the decode read consumes:
    compiled for the chip, the engine's decode program (and its verify
    program, a 5-token chunk through the same read) keeps temporaries
    under a tenth of the cache's bytes and no ``copy`` as large as one
    cache buffer. With the cache stored [S, L, Hkv, D] this read 515 MB
    of temporaries for 256 MB of cache and 8 such copies (4 to a
    lane-padded layout on the way in, 4 back): the round's 32 ms of
    ``copy.N`` and the reason 32 slots did not fit (PERF.md PR 27).
    The read is the bounded one (the engine's default on a linear
    cache), compiled as the chip compiles it: left to the backend it
    sees here, the kernel would be traced for the interpreter, a
    ``while`` that stages whole cache buffers."""
    eng = serve_chat_engine
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    args = [eng._params, eng._aux, eng._caches, eng._state]
    fn = eng._step_fn
    if program == "verify":
        s_ = eng.slots
        fn = eng._verify_fn
        args += [jnp.zeros((s_, eng.spec_k), I32), jnp.zeros((s_,), I32)]
    # donated as on the chip (the engine donates nothing on the CPU)
    compiled = jax.jit(fn, donate_argnums=(2, 3)) \
        .lower(*[abstract(a) for a in args]).compile()
    leaves = jax.tree_util.tree_leaves(eng._caches)
    cache_bytes = sum(x.nbytes for x in leaves)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.1 * cache_bytes, (temp, cache_bytes)
    # one kernel a layer, in the step's loop body
    assert compiled.as_text().count("tpu_custom_call") >= len(eng._caches)
    # `%copy.N = bf16[16,1024,2048]{...} copy(...)`: the result's dims
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
    big = [dims for dims in copies
           if np.prod([int(d) for d in dims.split(",")]) == leaves[0].size]
    assert not big, big


# -- ZAYA1-8B: the routed experts' kernel and the decode program ---------

@pytest.mark.parametrize("rows,blocks", [(16, 18), (128, 48)])
def test_grouped_matmul(compile_for_chip, rows, blocks):
    """The routed experts' product at ZAYA1-8B's widths (16 experts,
    gate and up of 2048 x 2048 in one matrix): a decode step's 32
    tokens in blocks of 16 rows, and 4096 prefill tokens in blocks of
    128."""
    def fn(x, w, block_e, used):
        return pk.grouped_matmul(x, w, block_e, used, rows,
                                 interpret=False)
    compile_for_chip(fn, ((blocks * rows, 2048), BF16),
                     ((16, 4096, 2048), BF16), ((blocks,), I32), ((), I32))


def test_zaya_decode_program(one_chip, monkeypatch):
    """The engine's decode program for ZAYA1-8B's block at its
    published widths (2 layers, a cut vocabulary, 32 slots x 2048
    rows), compiled for the chip: the routed experts run as the Pallas
    kernel, two calls a layer, and CCAttention's read as the bounded
    one, a third; nothing as large as a layer's expert stack is copied
    (XLA's own grouped product lays out a 134 MB temporary and
    multiplies every expert); the temporaries stay under a tenth of
    the cache; and no K/V buffer is staged through fast memory. Read
    densely, a layer's whole 33.5 MB buffer was an operand the compiler
    fetched into fast memory (``S(1)``) in four ``slice-start``s of a
    quarter each, scattered and read THERE, and sent back by a
    ``copy-start`` / ``copy-done`` of the whole buffer, on every step:
    25 ms of a 123 ms decode round under ``copy-done`` (PERF.md
    section 6, PR 31)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_zaya_lm
    layers = 2
    sym = get_zaya_lm(8192, layers, 2048, 8, 2, 128, 16, 2048, 256,
                      rotary_dim=64, rope_base=5e6)
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.zeros(s, BF16)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    dec = mx.parallel.Decoder(sym, params, max_len=2048,
                              compute_dtype="bfloat16")
    eng = mx.serving.InferenceEngine(
        dec, slots=32, prefill_buckets=(128, 512), steps_per_round=8,
        prefix_cache_mb=0, prefill_chunk=0)
    # the process sees the CPU; the program is traced as the chip would
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        [eng._params, eng._aux, eng._caches, eng._state])
    compiled = jax.jit(eng._make_step(), donate_argnums=(2, 3)) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 * layers
    # `%copy-start = (bf16[32,2048,256]{...}, bf16[32,2048,256]{...S(1)},
    # ...) copy-start(...)`, `%slice-start.4 = (..., bf16[8,2048,256]
    # {...S(1)}, ...) slice-start(...)`: a shape in fast memory on the
    # line of an asynchronous copy, of a buffer's rows: all 32 slots
    # or some of them (a weight's prefetch is the size of a quarter)
    rows = ",%d,%d" % eng._caches[0][0].shape[1:]
    staged = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"\b(copy-start|copy-done|slice-start)\(", line)
        for dims in re.findall(r"\w+\[([\d,]+)\]\{[^}]*S\(1\)[^}]*\}",
                               line)
        if dims.endswith(rows)]
    assert not staged, staged
    stack = 16 * 4096 * 2048
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert not [d for d in copies
                if np.prod([int(n) for n in d.split(",")]) >= stack]
    cache_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(eng._caches))
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.1 * cache_bytes
    eng.close()


# -- Qwen3-Next: a recurrent state and K/V rows in one decode program ----

@pytest.fixture(scope="module")
def longdoc_engine():
    """The benchmark's serve-longdoc engine at Qwen3-Next-80B-A3B's
    published widths cut to 2 layers, one of each kind (a Gated DeltaNet
    layer, a gated D=256 attention layer), 128 of 512 experts held, a
    cut vocabulary: 64 slots x 9216 rows, 8 steps a round, prompts in
    pieces of 2048. Weights are zeros: only shapes reach the compiler."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_qwen3_next_lm
    sym = get_qwen3_next_lm(
        8192, 2, 2048, num_heads=16, num_kv_heads=2, head_dim=256,
        linear_k_heads=16, linear_v_heads=32, linear_k_dim=128,
        linear_v_dim=128, num_experts=512, expert_hidden=512, top_k=10,
        shared_hidden=512, full_attention_interval=2, experts_held=128,
        rotary_dim=64, rope_base=1e7)
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.zeros(s, BF16)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    dec = mx.parallel.Decoder(sym, params, max_len=9216,
                              compute_dtype="bfloat16")
    eng = mx.serving.InferenceEngine(
        dec, slots=64, prefill_buckets=(512, 1024, 2048),
        steps_per_round=8, prefix_cache_mb=0, prefill_chunk=2048)
    yield eng
    eng.close()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def test_gdn_state_step(compile_for_chip):
    """The decode step's recurrence alone at the cell's shapes (a
    float32 leaf of 64 slots x 32 heads of 128 x 128, a slot's whole
    2 MB state a block): the in-kernel transpose that turns a block's k
    and q rows into columns, the sums over the sublanes and four blocks
    of 2 MB in flight are what interpret mode cannot see. The leaf is
    the call's aliased operand."""
    s, h, d = 64, 32, 128
    assert pk.default_state_block_h(h, d, d) == h

    def step(state, q, k, v, beta, g, lens, fresh):
        return pk.gdn_state_step(state, q, k, v, beta, g, lens, fresh,
                                 interpret=False)

    text = compile_for_chip(
        step, ((s, h, d, d), F32), *[((s, h, d), F32)] * 3,
        *[((s, h), F32)] * 2, ((s,), I32), ((s,), jnp.bool_))
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert "output_to_operand_aliasing" in call, call[:300]


def test_longdoc_decode_program(longdoc_engine, one_chip, monkeypatch):
    """The decode program of the cell's two kinds of layer, compiled for
    the chip: the attention layer's read is the bounded kernel, the
    routed experts two grouped products and the DeltaNet layer's state
    step the kernel that visits live slots only (six kernels in the
    step's body); the 134 MB float32 state leaf is updated where it
    lies: no ``copy`` of it, not staged through fast memory, the
    temporaries under a tenth of the caches, and NO fusion takes the
    leaf as an operand: nothing outside the kernel reads or writes it
    (a ``where`` over it, for the zero start or for the slots that are
    not live, is a pass over every slot's state)."""
    eng = longdoc_engine
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    args = _abstract([eng._params, eng._aux, eng._caches, eng._state],
                     one_chip)
    compiled = jax.jit(eng._make_step(), donate_argnums=(2, 3)) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6
    state = eng._caches[0][0]
    assert state.shape == (64, 32, 128, 128) and state.dtype == F32
    dims = ",".join(str(d) for d in state.shape)
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert dims not in copies, copies
    staged = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"\b(copy-start|copy-done|slice-start)\(", line)
              and re.search(r"f32\[[\d,]*32,128,128\]\{[^}]*S\(1\)", line)]
    assert not staged, staged
    leaf = "f32[%s]" % dims
    fused = [line.strip()[:200] for line in text.splitlines()
             if leaf in line
             and re.search(r"fused_computation|\bfusion\(", line)]
    assert not fused, fused
    cache_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(eng._caches))
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.1 * cache_bytes


def test_longdoc_prefill_program(longdoc_engine, one_chip, monkeypatch):
    """The largest prefill program of the cell (the 2,048-token piece),
    compiled for the chip: the chunked recurrence's triangular solve, the
    blocked attention read against 9,216 rows and the routed experts in
    four passes of 512 tokens compile, and the temporaries stay under
    0.5 GB (2,048 queries against 9,216 rows at once would be 1.2 GB of
    scores alone). No buffer the compiler keeps in fast memory is over
    64 MiB of the chip's 128 (the chunked recurrence's products, which
    run): the routed layout of all 20,480 pairs at once was 112 MiB
    there and the eight-layer program never came back (PERF.md section
    6, PR 34)."""
    from mxnet_tpu.serving.engine import _raw_key
    eng = longdoc_engine
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    bucket = 2048
    fn = eng._prefill_fn(bucket)
    i32 = np.int32
    args = [eng._params, eng._aux, eng._caches, eng._state, i32(0),
            jnp.zeros((1, bucket), I32), i32(0), i32(bucket),
            np.bool_(True), np.float32(0), _raw_key(0), i32(-1), i32(8)]
    compiled = jax.jit(fn, donate_argnums=(2, 3)) \
        .lower(*_abstract(args, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    fast = [(int(np.prod([int(d) for d in dims.split(",")])) * width[kind],
             kind, dims)
            for kind, dims in re.findall(
                r"\b(bf16|f32|s32|u32|pred)\[([\d,]+)\]\{[^}]*S\(1\)", text)]
    assert fast and max(fast)[0] <= 64 << 20, max(fast)


# -- Xing4.0: latent rows, read once a block, in one decode program -------

@pytest.mark.parametrize("chunk", [1, 5])
def test_latent_paged_attention(compile_for_chip, chunk):
    """The latent read at the published widths: 32 heads against rows of
    576 bf16 numbers (not whole lane tiles: 512 + 64), stored as they are
    and stored padded to 640 as the decoder stores them, 48 slots x 9,216
    rows, values the first 512."""
    s, l, h, w, r = 48, 9216, 32, 576, 512
    for stored in (w, 640):
        compile_for_chip(
            lambda q, c, pos, lens: pk.latent_paged_attention(
                q, c, pos, v_width=r, scale=0.1447, lens=lens,
                interpret=False),
            ((s, chunk, h, w), BF16), ((s, l, stored), BF16), ((s,), I32),
            ((s,), I32))


@pytest.fixture(scope="module")
def longctx_engine():
    """The benchmark's serve-longctx engine at Xing4.0-29B-A4B's published
    widths cut to 2 layers, one of each kind (a dense layer, a routed
    layer with 16 of 64 experts held), a cut vocabulary: 48 slots x 9216
    rows, 8 steps a round, prompts in pieces of 1024. Weights are zeros:
    only shapes reach the compiler."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_xing4_lm
    sym = get_xing4_lm(
        8192, 2, 3584, num_heads=32, q_lora_rank=768, kv_lora_rank=512,
        nope_dim=128, rope_dim=64, v_dim=128, ffn_hidden=9216,
        num_experts=64, expert_hidden=1024, top_k=4, shared_hidden=1024,
        dense_layers=1, route_scale=2.0, experts_held=16, lanes=4,
        hc_res_diag=2.0, yarn=(64.0, 4096, 32.0, 1.0), mscale_all_dim=1.0)
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.zeros(s, BF16)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    dec = mx.parallel.Decoder(sym, params, max_len=9216,
                              compute_dtype="bfloat16")
    eng = mx.serving.InferenceEngine(
        dec, slots=48, prefill_buckets=(256, 512, 1024),
        steps_per_round=8, prefix_cache_mb=0, prefill_chunk=1024)
    yield eng
    eng.close()


def test_longctx_decode_program(longctx_engine, one_chip, monkeypatch):
    """The decode program of the cell's two kinds of layer, compiled for
    the chip: each layer's latent read is the one kernel (no second fetch
    of the rows: one custom call a layer) and the routed layer's experts
    two grouped products (four kernels in the step's body); the 566 MB
    buffer of latent rows is written where it lies: no ``copy`` of it
    (declared 576 wide and not 640, the compiler laid it out rows-minor
    and copied it whole on the way in and out), and the temporaries stay
    under a tenth of the caches."""
    eng = longctx_engine
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    args = _abstract([eng._params, eng._aux, eng._caches, eng._state],
                     one_chip)
    compiled = jax.jit(eng._make_step(), donate_argnums=(2, 3)) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    rows = eng._caches[0][0]
    assert rows.shape == (48, 9216, 640) and rows.dtype == BF16
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert "48,9216,640" not in copies, copies
    cache_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(eng._caches))
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.1 * cache_bytes


def test_longctx_prefill_program(longctx_engine, one_chip, monkeypatch):
    """The largest prefill program of the cell (the 1,024-token piece),
    compiled for the chip: the expanded read a block of 1,024 latent rows
    at a time in a loop whose length follows the position, the routed
    experts in one pass, the hyper-connections over 1,024 x 4 lanes; the
    temporaries stay under 1 GB and no buffer the compiler keeps in fast
    memory is over 64 MiB of the chip's 128."""
    from mxnet_tpu.serving.engine import _raw_key
    eng = longctx_engine
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    bucket = 1024
    fn = eng._prefill_fn(bucket)
    i32 = np.int32
    args = [eng._params, eng._aux, eng._caches, eng._state, i32(0),
            jnp.zeros((1, bucket), I32), i32(0), i32(bucket),
            np.bool_(True), np.float32(0), _raw_key(0), i32(-1), i32(8)]
    compiled = jax.jit(fn, donate_argnums=(2, 3)) \
        .lower(*_abstract(args, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    fast = [(int(np.prod([int(d) for d in dims.split(",")])) * width[kind],
             kind, dims)
            for kind, dims in re.findall(
                r"\b(bf16|f32|s32|u32|pred)\[([\d,]+)\]\{[^}]*S\(1\)", text)]
    assert fast and max(fast)[0] <= 64 << 20, max(fast)
