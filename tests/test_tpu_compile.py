"""The main path's Pallas kernels, compiled for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`topologies.get_topology_desc`). Nothing runs, so
this says nothing about results or times: it holds the ROUTER to the
COMPILER. Every kernel instance `conv_decision` / `bn_supported` /
`bn_bwd_supported` would select for ResNet-50 (batch 128, 224², bf16) and
the three LSTM kernels at the char-LSTM width must compile, forward and
backward; every shape the compiler refuses must be "unsupported" in
`conv_decision` by shape. Interpret-mode tests cannot see any of this.

The last section holds the XLA side of the step program to the compiler
the same way: what `nn/layers/conv._pool` counts on (a max pool's backward
as one elementwise fusion, no select-and-scatter, nothing full-size
written twice) is a property of the chip's fusion pass, and only a
compile for the chip shows it. The same for the Mamba-2 mixer's two
elementwise stages (`nn/layers/ssm.conv_silu`, `gate_norm`): that their
hand-written passes stay single lane-dense passes is the compiler's doing.
And for a window layer of attention at the SmallThinker cell's shapes: that
the blocks past the window are one loop and no score tensor spans all keys.

This is the only test file that describes the chip, and it does so inside
a module-scoped fixture: only one process may load the TPU library, so a
call at import or collection time would break the other xdist workers.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import pallas_conv_bn as pcb
from deeplearning4j_tpu.ops import pallas_lstm

BATCH = 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile fn for the described chip; shapes are (shape, dtype)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# -- ResNet-50: what the router selects ---------------------------------------

@functools.cache
def _resnet50_rows():
    from deeplearning4j_tpu.analysis.kernelcoverage import coverage_table
    from deeplearning4j_tpu.models.resnet import resnet50_conf

    return coverage_table(
        resnet50_conf(num_classes=1000, image_size=224, precision="bf16"),
        batch=BATCH)


def _covered_conv_instances(family):
    """Distinct (x_shape, n_out, stride) the router covers in a family."""
    return sorted({(tuple(r["x_shape"]), r["n_out"], tuple(r["stride"]))
                   for r in _resnet50_rows()
                   if r["status"] == "covered" and r["family"] == family})


def _bn_shapes():
    """Distinct NHWC outputs of the covered convs: the BN layers fed from
    the stats stash (`bn_supported`). `bn_bwd_supported` takes every BN
    with 64-aligned channels, i.e. the output of every conv."""
    covered, every = set(), set()
    for r in _resnet50_rows():
        n, h, w, _ = r["x_shape"]
        sh, sw = r["stride"]
        out = (n, -(-h // sh), -(-w // sw), r["n_out"])
        every.add(out)
        if r["status"] == "covered":
            covered.add(out)
    return sorted(covered), sorted(every)


def _compile_conv(one_chip, x_shape, w_shape, stride, backward):
    """conv2d_bn_stats alone, or loss and pullback in one program as a
    train step has them (the pullback alone needs no forward output, and
    the kernel would be eliminated as dead code)."""
    def fwd(x, w):
        return pcb.conv2d_bn_stats(x, w, stride)

    def bwd(x, w):
        return jax.value_and_grad(
            lambda x, w: jnp.sum(fwd(x, w)[0].astype(jnp.float32)),
            argnums=(0, 1))(x, w)

    _compile(one_chip, bwd if backward else fwd,
             (x_shape, BF16), (w_shape, BF16))


def _conv_case(family, backward):
    def run(one_chip):
        instances = _covered_conv_instances(family)
        assert instances, f"router covers no {family} instance"
        for x_shape, n_out, stride in instances:
            _compile_conv(one_chip, x_shape, (1, 1, x_shape[3], n_out),
                          stride, backward)
    return run


def _bn_apply_case(one_chip):
    covered, _ = _bn_shapes()
    assert covered
    for shape in covered:
        c = shape[-1]
        n = shape[0] * shape[1] * shape[2]
        for relu in (False, True):
            _compile(
                one_chip,
                lambda x, s1, s2, g, b, n=n, relu=relu: pcb.bn_apply(
                    x, s1, s2, g, b, 1e-5, n, relu),
                (shape, BF16), ((c,), jnp.float32), ((c,), jnp.float32),
                ((c,), jnp.float32), ((c,), jnp.float32))


def _bn_bwd_case(one_chip):
    _, every = _bn_shapes()
    for shape in every:
        c = shape[-1]
        n = shape[0] * shape[1] * shape[2]
        _compile(
            one_chip,
            lambda g, x, center, gamma, inv, n=n: pcb.bn_backward_fused(
                g, x, center, gamma, inv, n),
            (shape, BF16), (shape, BF16), ((c,), jnp.float32),
            ((c,), jnp.float32), ((c,), jnp.float32))


def _ck_allowed_case(backward):
    """A kxk instance the structural stage allows (the roofline declines
    every such ResNet-50 instance at batch 128, other nets may not)."""
    ctx = dict(kernel=(3, 3), stride=(1, 1), dilation=(1, 1), same=True,
               has_bias=False, activation="identity", dtype=BF16, n_in=128,
               n_out=128, x_shape=(BATCH, 28, 28, 128), training=True)

    def run(one_chip):
        assert pcb.conv_decision(planning=True, **ctx)["status"] \
            != "unsupported"
        _compile_conv(one_chip, ctx["x_shape"], (3, 3, 128, 128), (1, 1),
                      backward)
    return run


# -- char-LSTM: T=50 (TBPTT), B=64, H=200; decode with 4 slots ----------------

T, B, H, SLOTS = 50, 64, 200, 4


def _lstm_args(lead, dtype):
    vec = ((H,), dtype)
    return [(lead + (4 * H,), dtype), ((H, 4 * H), dtype), vec, vec, vec,
            (lead[-1:] + (H,), dtype), (lead[-1:] + (H,), dtype)]


def _lstm_case(kind, dtype):
    def run(one_chip):
        if kind == "step":
            _compile(one_chip, pallas_lstm.lstm_step,
                     *_lstm_args((SLOTS,), dtype))
            return

        def fwd(*a):
            return pallas_lstm.lstm_sequence(*a)

        def bwd(*a):
            def loss(*a):
                y, h_f, c_f = pallas_lstm.lstm_sequence(*a)
                return (jnp.sum(y.astype(jnp.float32))
                        + jnp.sum(h_f.astype(jnp.float32))
                        + jnp.sum(c_f.astype(jnp.float32)))
            return jax.grad(loss, argnums=tuple(range(7)))(*a)

        _compile(one_chip, bwd if kind == "bwd" else fwd,
                 *_lstm_args((T, B), dtype))
    return run


KERNEL_CASES = {
    "conv1x1-fwd": _conv_case("conv1x1", backward=False),
    "conv1x1-bwd": _conv_case("conv1x1", backward=True),
    "conv1x1s2-fwd": _conv_case("conv1x1s2", backward=False),
    "conv1x1s2-bwd": _conv_case("conv1x1s2", backward=True),
    "bn_apply-fwd": _bn_apply_case,
    "bn_bwd": _bn_bwd_case,
    "conv3x3-allowed-fwd": _ck_allowed_case(backward=False),
    "conv3x3-allowed-bwd": _ck_allowed_case(backward=True),
    "lstm_seq-fwd-f32": _lstm_case("fwd", jnp.float32),
    "lstm_seq-bwd-f32": _lstm_case("bwd", jnp.float32),
    "lstm_step-f32": _lstm_case("step", jnp.float32),
    "lstm_seq-fwd-bf16": _lstm_case("fwd", BF16),
    "lstm_seq-bwd-bf16": _lstm_case("bwd", BF16),
    "lstm_step-bf16": _lstm_case("step", BF16),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_routed_kernel_compiles_for_v5e(case, one_chip):
    KERNEL_CASES[case](one_chip)


def test_router_covers_no_other_family_on_resnet50():
    """The cases above are all there is: on the chip the router selects
    only the 1x1 kernels for ResNet-50 at batch 128."""
    fams = {r["family"] for r in _resnet50_rows()
            if r["status"] == "covered"}
    assert fams == {"conv1x1", "conv1x1s2"}


# -- what the compiler refuses, the router refuses by shape -------------------

def _conv_ctx(kernel, stride, x_shape, n_out):
    return dict(kernel=kernel, stride=stride, dilation=(1, 1), same=True,
                has_bias=False, activation="identity", dtype=BF16,
                n_in=x_shape[3], n_out=n_out, x_shape=x_shape, training=True)


# reason -> (conv context, compile it to see the refusal?)
REFUSED = {
    # ResNet-50's stem and its 56x56 stage entry: a strided vector slice
    "strided_taps-stem": (_conv_ctx((7, 7), (2, 2),
                                    (BATCH, 224, 224, 3), 64), True),
    "strided_taps-3x3s2": (_conv_ctx((3, 3), (2, 2),
                                     (BATCH, 56, 56, 128), 128), True),
    # ResNet-50's stage-0 3x3: 64 input channels half-fill the lanes
    "lane_alignment": (_conv_ctx((3, 3), (1, 1),
                                 (BATCH, 56, 56, 64), 64), True),
    # compiling this one does not end (stopped after 40 minutes), so only
    # the decision is asserted
    "image_rows": (_conv_ctx((3, 3), (1, 1),
                             (BATCH, 56, 56, 128), 128), False),
    # 16 MiB of weights alone: over the compiler's scoped-VMEM limit
    # (seen on the kernel called by itself; inside a larger program XLA
    # may place its operands otherwise and let it pass)
    "vmem": (_conv_ctx((1, 1), (1, 1), (BATCH, 7, 7, 2048), 4096), True),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refused_shape_is_unsupported_by_shape(case, one_chip):
    ctx, compile_it = REFUSED[case]
    d = pcb.conv_decision(planning=True, **ctx)
    assert d["status"] == "unsupported"
    assert d["reason"] == case.split("-")[0]
    assert d["reason"] in pcb.CHIP_REFUSALS
    if not compile_it:
        return
    kh, kw = ctx["kernel"]
    with pytest.raises(Exception) as err:
        if (kh, kw) == (1, 1):
            n, h, w, cin = ctx["x_shape"]
            _compile(one_chip, pcb._mm_stats_call,
                     ((n * h * w, cin), BF16), ((cin, ctx["n_out"]), BF16))
        else:
            _compile_conv(one_chip, ctx["x_shape"],
                          (kh, kw, ctx["n_in"], ctx["n_out"]),
                          ctx["stride"], backward=False)
    # the compiler's refusal, not an error of this test's own making
    assert any(s in str(err.value) for s in (
        "extract_strided_slice", "unsupported shape cast",
        "exceeded scoped vmem limit")), str(err.value)[:400]


# -- the max pool's backward pass as the chip's compiler fuses it --------------

def _vgg_front_hlo(one_chip, pool):
    """Optimized HLO of an SGD step over VGG16's first three convs
    (3 -> 64 -> 64 -> pool -> 128 -> pool) at batch 128, 224x224, bf16,
    through the layers' own forward functions."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.layers import conv as C
    from deeplearning4j_tpu.nn.layers.registry import LayerContext

    ctx = LayerContext(training=True)
    convs = [L.ConvolutionLayer(n_in=i, n_out=o, kernel_size=(3, 3),
                                convolution_mode="same", activation="relu")
             for i, o in ((3, 64), (64, 64), (64, 128))]

    def loss(params, x):
        for i, (conf, p) in enumerate(zip(convs, params)):
            x = C.conv_forward(conf, p, x, ctx)[0]
            if i > 0:
                x = C.subsampling_forward(pool, {}, x, ctx)[0]
        return jnp.mean(x.astype(jnp.float32) ** 2)

    def step(params, x):
        grads = jax.grad(loss)(params, x)
        return jax.tree.map(lambda p, g: p - 0.1 * g.astype(p.dtype),
                            params, grads)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    params = [{"W": sds(3, 3, c.n_in, c.n_out), "b": sds(c.n_out)}
              for c in convs]
    return jax.jit(step).lower(params, sds(BATCH, 224, 224, 3)) \
        .compile().as_text()


def _named_entry_ops(hlo):
    """name -> (result type, opcode, operand names, `op_name`) of the entry
    computation."""
    entry = hlo[hlo.index("ENTRY"):]
    ops = {}
    for name, result, op, args, rest in re.findall(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)(?:, (.*))?$",
            entry[:entry.index("\n}")], re.M):
        scope = re.search(r'op_name="([^"]*)"', rest)
        ops[name] = (result, op, re.findall(r"%[\w.\-]+", args),
                     scope.group(1) if scope else "")
    return ops


def _entry_ops(hlo):
    """(name, result type, opcode, operands) of the entry computation."""
    return [(name, result, op, args)
            for name, (result, op, args, _) in _named_entry_ops(hlo).items()]


def test_tiling_max_pool_backward_is_one_select_fusion(one_chip):
    from deeplearning4j_tpu.nn.conf import layers as L

    ops = _entry_ops(_vgg_front_hlo(one_chip, L.SubsamplingLayer()))
    types = {name: result for name, result, _, _ in ops}
    assert not any(op == "select-and-scatter" for _, _, op, _ in ops)
    # nothing but a fusion writes a tensor of the pools' input sizes: no
    # broadcast, copy or transpose of the window view or the 4-D tensor
    full = re.compile(r"\[128,(224,224,64|112,2,112,2,64"
                      r"|112,112,128|56,2,56,2,128)\]")
    writers = {op for _, result, op, _ in ops if full.search(result)}
    assert writers <= {"fusion", "bitcast", "get-tuple-element"}, writers
    for view, pooled in (("[128,112,2,112,2,64]", "[128,112,112,64]"),
                         ("[128,56,2,56,2,128]", "[128,56,56,128]")):
        selects = [(name, args) for name, result, op, args in ops
                   if op == "fusion" and result.startswith("bf16" + view)]
        # one fusion writes the pool's input gradient, in the window view,
        # from the int8 index and the pooled gradient alone
        assert len(selects) == 1, selects
        read = sorted(types[a].split("{")[0] for a in selects[0][1]
                      if "[128," in types.get(a, ""))
        assert read == ["bf16" + pooled, "s8" + pooled], read
    # the convs in front of the pools write one tensor, the pre-activation:
    # the ReLU works behind the pool
    for name, result, op, _ in ops:
        assert not re.match(
            r"\(bf16\[128,224,224,64\]\S*, bf16\[128,224,224,64\]", result), name


def test_overlapping_max_pool_keeps_select_and_scatter(one_chip):
    from deeplearning4j_tpu.nn.conf import layers as L

    pool = L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2),
                              convolution_mode="same")
    ops = _entry_ops(_vgg_front_hlo(one_chip, pool))
    assert sum(op == "select-and-scatter" for _, _, op, _ in ops) == 2
    assert not any("s8[128," in result for _, result, _, _ in ops)


# -- the Mamba-2 mixer's elementwise stages as the chip's compiler fuses them ----

_WIDTH = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
          "pred": 1}
_MOVES_NOTHING = {"parameter", "get-tuple-element", "bitcast", "tuple",
                  "constant", "after-all"}
_LAYOUT_ONLY = {"bitcast", "copy", "reshape", "transpose"}


def _nbytes(result):
    """Bytes of an HLO result type, every member of a tuple counted."""
    total = 0
    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", result):
        n = _WIDTH.get(dtype, 0)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def _mixer_hlo(one_chip):
    """Optimized HLO of one mixer at the Nemotron cell's shapes as a block
    of the step runs it: forward, the forward again under `jax.checkpoint`
    and the backward pass, bf16 products on a float32 residual stream."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.layers import ssm
    from deeplearning4j_tpu.nn.layers.registry import LayerContext

    conf = L.Mamba2Layer(n_in=2688, n_out=2688, n_heads=64, head_dim=64,
                         state_size=128, n_groups=8, conv_kernel=4,
                         chunk_size=128, norm_eps=1e-5, weight_init="xavier")
    ctx = LayerContext(training=True, compute_dtype=BF16)

    def loss(params, x):
        mixer = jax.checkpoint(
            lambda p, x: ssm.mamba2_forward(conf, p, x, ctx)[0])
        return jnp.sum(jnp.square(x + mixer(params, x)))

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: ssm.mamba2_init(jax.random.PRNGKey(0), conf, jnp.float32)))
    x = jax.ShapeDtypeStruct((4, 4096, 2688), jnp.float32, sharding=one_chip)
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x) \
        .compile().as_text()


def test_mixer_stages_stay_lane_dense_single_passes(one_chip):
    ops = _named_entry_ops(_mixer_hlo(one_chip))
    in_scan = lambda name: "ssd_scan" in ops[name][3]
    users = {}
    for name, (_, _, operands, _) in ops.items():
        for a in operands:
            users.setdefault(a, []).append(name)

    def only_feeds_the_scan(name):
        """Through layout changes alone into ops of the scan's scope."""
        return bool(users.get(name)) and all(
            in_scan(u) or (ops[u][1] in _LAYOUT_ONLY
                           and only_feeds_the_scan(u))
            for u in users[name])

    full = 4 * 4096 * 4096 * 4            # f32[4,4096,4096] or another view
    outside = 0
    for name, (result, op, operands, scope) in ops.items():
        # the conv's backward keeps ONE float32 tensor of its size (dpre)
        assert result.count("f32[4,4096,6144]") <= 1, name
        # the group statistic goes back to the channels inside a fusion
        assert not (op == "broadcast" and "f32[4,4096,8,512]" in result), name
        # no head-shaped view of a full-size tensor but the scan's own
        if op in ("reshape", "copy", "transpose") \
                and result.startswith("f32") and _nbytes(result) == full:
            assert in_scan(name) or only_feeds_the_scan(name), (name, scope)
        if op not in _MOVES_NOTHING and not in_scan(name):
            outside += sum(_nbytes(ops[a][0]) for a in operands if a in ops)
            outside += 0 if op.endswith("-start") else _nbytes(result)
    # operand and result bytes of every top-level op outside the `ssd_scan`
    # scope (a fused slice counts its whole operand): 21.4 GB as committed,
    # 35.7 GB with the stages left to autodiff (the parent of PR 31)
    assert outside < 23.5e9, outside


# -- window attention at the SmallThinker cell's shapes ---------------------------

def _attention_hlo(one_chip, **kind):
    """Optimized HLO and memory of one attention layer at the SmallThinker
    cell's shapes as a sub-block of the step runs it: forward, the forward
    again under `jax.checkpoint`, backward."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.layers import attention
    from deeplearning4j_tpu.nn.layers.registry import LayerContext

    conf = L.GroupedQueryAttentionLayer(
        n_in=2560, n_out=2560, n_heads=28, n_kv_heads=4, head_dim=128,
        weight_init="xavier", **kind)
    ctx = LayerContext(training=True, compute_dtype=BF16)

    def loss(params, x):
        layer = jax.checkpoint(
            lambda p, x: attention.gqa_forward(conf, p, x, ctx)[0])
        return jnp.sum(jnp.square(x + layer(params, x)))

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: attention.gqa_init(jax.random.PRNGKey(0), conf, jnp.float32)))
    x = jax.ShapeDtypeStruct((2, 8192, 2560), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x) \
        .compile()
    return compiled.as_text(), compiled.memory_analysis()


_NO_TILING = '"estimated_cycles":"9223372036854775807"'


def test_the_full_layers_wide_blocks_find_a_tiling(one_chip):
    """A block of the full layer meets up to 8,192 keys. With the row
    maximum fused into `jax.nn.softmax`'s subtraction the compiler finds no
    tiling from 5,376 keys on (its cost estimate overflows; on the chip such
    a fusion took 41 ms where 4,352 keys take 1.1: PERF.md, PR 32), so those
    blocks take the maximum in a pass of its own (`attention.WIDE_KEYS`)."""
    from deeplearning4j_tpu.nn.layers import attention

    hlo, memory = _attention_hlo(one_chip)
    assert "full_attention" in hlo and "rope" not in hlo
    assert re.search(r"f32\[2,4,7,256,8192\]", hlo)
    assert _NO_TILING not in hlo
    assert memory.temp_size_in_bytes < 3.0e9
    # the compiler still has the fault this works around: when it is gone,
    # `WIDE_KEYS` and `_softmax_max_apart` can go too
    was = attention.WIDE_KEYS
    attention.WIDE_KEYS = 1 << 30
    try:
        fused, _ = _attention_hlo(one_chip)
    finally:
        attention.WIDE_KEYS = was
    assert fused.count(_NO_TILING) == 24      # 12 wide blocks, twice each


def test_a_window_layer_is_one_scanned_body_past_the_window(one_chip):
    """One window layer of the cell `smallthinker_21b_train_b2_s8192` as a
    sub-block of the step runs it (forward, the forward again under
    `jax.checkpoint`, backward): the chip's compiler takes the rotation,
    the sixteen unrolled blocks and the scanned body of the other sixteen,
    and a block's float32 scores `[2, 4, 7, 256, <= 4352]` are the largest
    thing alive, not a layer's."""
    hlo, memory = _attention_hlo(one_chip, window=4096, rope_theta=1.5e6)
    # the steady blocks: one loop forward, one recomputed, one backward
    loops = [l for l in hlo.splitlines()
             if re.search(r"= .* while\(", l) and "window_attention" in l]
    assert len(loops) == 3, len(loops)
    assert "rope" in hlo
    # no score tensor over all 8,192 keys: the band is followed
    assert not re.search(r"f32\[2,4,7,256,8192\]", hlo)
    assert re.search(r"f32\[2,4,7,256,4352\]", hlo)
    # a block's scores are 0.25 GB; a layer's would be 7.7
    assert _NO_TILING not in hlo
    assert memory.temp_size_in_bytes < 3.0e9


# -- the fused attention kernel at both decoder cells' shapes -----------------------

FUSED_ATTENTION = {
    "smallthinker_window": ((2, 8192, 28, 4), 4096),
    "smallthinker_full": ((2, 8192, 28, 4), None),
    "nemotron_full": ((4, 4096, 32, 2), None),
    # the latent layer: queries and keys of 192, values of 128, a group of 1
    "kanana_latent": ((2, 8192, 32, 32), None, (192, 128)),
}


@pytest.mark.parametrize("case", sorted(FUSED_ATTENTION))
def test_fused_attention_compiles_for_v5e(case, one_chip):
    """`ops/pallas_attention.gqa_attention` as a recomputed block runs it
    (forward, the forward again under `jax.checkpoint`, backward) at the
    shapes its probe takes from the three decoder cells: the chip's compiler
    takes both kernels at tiles of 1,024 with `dk` and `dv` of one key-value
    head resident, and nothing of `[queries, keys]` is left in the program
    around them."""
    from deeplearning4j_tpu.ops import pallas_attention

    (b, t, heads, kv_heads), window, *sizes = FUSED_ATTENTION[case]
    d_qk, d_v = sizes[0] if sizes else (128, 128)
    assert pallas_attention._block(t) == 1024

    def loss(q, k, v):
        attend = jax.checkpoint(lambda q, k, v: pallas_attention.gqa_attention(
            q, k, v, causal=True, window=window))
        return jnp.sum(jnp.square(attend(q, k, v)))

    arg = lambda n, d: jax.ShapeDtypeStruct((b, t, n, d), BF16,
                                            sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(heads, d_qk), arg(kv_heads, d_qk), arg(kv_heads, d_v)).compile()
    hlo = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"', hlo)
    assert len(calls) == 3, len(calls)       # forward twice, backward once
    assert "gqa_fwd" in hlo and "gqa_bwd" in hlo
    # no array with two sequence-sized dimensions: the scores stay in VMEM
    assert not re.search(rf"\[[\d,]*{t},[\d,]*{t}[\d,]*\]", hlo)
    # q, k, v, o, the cotangents and two statistics a query: under 1 GB
    # (1.3 at the latent layer's 32 heads of 192 and 128)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.3e9 if sizes else 1.0e9)


# -- the fused grouped experts at the two gated cells' shapes ---------------------

FUSED_EXPERTS = {
    # held experts, rows of a buffer, n_in, width, activation
    "kanana": (16, 6144, 2048, 768, "silu", True),
    "smallthinker": (8, 16384, 2560, 768, "relu", True),
    # a two-matrix layer of whole lanes, and the Nemotron cell's 14.5
    "two_matrix": (8, 6144, 2688, 1792, "relu2", False),
    "nemotron": (8, 6144, 2688, 1856, "relu2", False),
}


@pytest.mark.parametrize("case", sorted(FUSED_EXPERTS))
def test_fused_experts_compile_for_v5e(case, one_chip):
    """`ops/pallas_experts.grouped_experts` as a recomputed block runs it,
    at the shapes its probe takes from the gated decoder cells: the chip's
    compiler takes the three kernels at the probe's own row tile with an
    expert's matrices and `dW2` resident in one buffer each, and no float32
    array of `[held, rows, width]` is left in the program around them."""
    from deeplearning4j_tpu.ops import pallas_experts

    held, cap, d, width, activation, gated = FUSED_EXPERTS[case]
    assert pallas_experts._tile(cap, d, width, gated) == (
        256 if gated else 128)

    def grads(rows, w1, w3, w2, slot_w, count, ct):
        run = jax.checkpoint(
            lambda r, a, b, c, s: pallas_experts.grouped_experts(
                r, a, b, c, s, count, activation=activation))
        out, pull = jax.vjp(run, rows, w1, w3, w2, slot_w)
        return (out,) + pull(ct)

    arg = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(grads).lower(
        arg((held, cap, d)), arg((held, d, width)),
        arg((held, d, width)) if gated else None, arg((held, width, d)),
        arg((held * cap,), jnp.float32), arg((held,), jnp.int32),
        arg((held, cap, d), jnp.float32)).compile()
    hlo = compiled.as_text()
    for name in ("experts_fwd", "experts_bwd_rows", "experts_bwd_weights"):
        assert name in hlo
    assert not re.search(rf"f32\[{held},{cap},{width}\]", hlo)


HELD_EXPERTS = dict(FUSED_EXPERTS, trinity=(16, 8192, 2048, 1024, "silu", True),
                    # 2,304 slots: not whole blocks of 1,024 in SMEM
                    slots_padded=(3, 768, 2048, 768, "silu", True))


@pytest.mark.parametrize("case", sorted(HELD_EXPERTS))
def test_held_experts_compile_for_v5e(case, one_chip):
    """`ops/pallas_experts.held_experts` (dispatch, the three products,
    combine) as a recomputed block runs it, at the decoder cells' shapes and
    16,384 tokens: the chip's compiler takes one DMA a row on the float32
    `[tokens, 1, n_in]` view, no gather or scatter over the buffers' rows
    is left in the program, and every kernel's HLO `op_name`, which the
    benchmark's scoped trace reads, keeps the layer's `experts` scope
    through the jitted dispatch and combine."""
    from deeplearning4j_tpu.ops import pallas_experts

    held, cap, d, width, activation, gated = HELD_EXPERTS[case]
    tokens = 16384

    def experts(x, slot_token, count, a, b, c, s):
        with jax.named_scope("L1_sparseexperts"), jax.named_scope("experts"):
            return pallas_experts.held_experts(
                x, slot_token, count, a, b, c, s, activation=activation)

    def grads(u, slot_token, count, w1, w3, w2, slot_w, ct):
        run = jax.checkpoint(
            lambda x, a, b, c, s: experts(x, slot_token, count, a, b, c, s))
        out, pull = jax.vjp(run, u, w1, w3, w2, slot_w)
        return (out,) + pull(ct)

    arg = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(grads).lower(
        arg((tokens, d)), arg((held * cap,), jnp.int32),
        arg((held,), jnp.int32), arg((held, d, width)),
        arg((held, d, width)) if gated else None, arg((held, width, d)),
        arg((held * cap,), jnp.float32), arg((tokens, d), jnp.float32),
    ).compile()
    hlo = compiled.as_text()
    for name in ("experts_dispatch", "experts_fwd", "experts_bwd_rows",
                 "experts_bwd_weights", "experts_combine"):
        assert name in hlo
    assert f"f32[{tokens},1,{d}]" in hlo
    assert not re.search(r"= \S+ (gather|scatter)\(", hlo)
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(kernels) >= 7
    assert all(re.search(r"L1_sparseexperts\)?/experts/", n)
               for n in kernels), kernels


# -- the Mamba-2 scan's kernels at the Nemotron cell's mixer ---------------------

def test_scan_kernels_compile_for_v5e_inside_the_scans_scope(one_chip,
                                                              monkeypatch):
    """One mixer at the cell's shapes as a block of the step runs it, with
    the op slot "ssd_scan" taking the kernels (the probe sees the CPU here,
    so the test steers the slot): the chip's compiler takes `ssd_fwd` (the
    forward, the forward again for `gate_norm`'s `y`, and the backward
    pass's sweep that makes the states) and `ssd_bwd`; each kernel's HLO
    `op_name`, which the benchmark's scoped trace reads, lies inside the
    layer's `ssd_scan` scope; and no chunk's `[Q, Q]` matrix is left in the
    program around them."""
    from benchmark import scope_reduce
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.layers import ssm
    from deeplearning4j_tpu.nn.layers.registry import LayerContext
    from deeplearning4j_tpu.ops import helpers, pallas_ssd

    monkeypatch.setitem(helpers._HELPERS, "ssd_scan", helpers.Helper(
        name="pallas_ssd_scan", fn=pallas_ssd.ssd_scan))
    conf = L.Mamba2Layer(n_in=2688, n_out=2688, n_heads=64, head_dim=64,
                         state_size=128, n_groups=8, conv_kernel=4,
                         chunk_size=128, norm_eps=1e-5, weight_init="xavier")
    ctx = LayerContext(training=True, compute_dtype=BF16)

    def loss(params, x):
        mixer = jax.checkpoint(
            lambda p, x: ssm.mamba2_forward(conf, p, x, ctx)[0])
        with jax.named_scope("Lb0_mixer_mamba2"):
            return jnp.sum(jnp.square(x + mixer(params, x)))

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: ssm.mamba2_init(jax.random.PRNGKey(0), conf, jnp.float32)))
    x = jax.ShapeDtypeStruct((4, 4096, 2688), jnp.float32, sharding=one_chip)
    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in hlo.splitlines() if "tpu_custom_call" in line]
    names = [re.search(r"/(ssd_\w+)/pallas_call", n).group(1)
             for n in kernels]
    assert sorted(names) == ["ssd_bwd"] + ["ssd_fwd"] * 3, kernels
    in_layer = scope_reduce.of_layer_kinds("mamba2")
    in_scan = scope_reduce.of_component("ssd_scan")
    assert all(in_layer(n) and in_scan(n) for n in kernels), kernels
    assert not re.search(r"f32\[[\d,]*128,128\]", hlo)
