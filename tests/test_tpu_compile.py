"""Compile for the chip without the chip (on-chip-measurement guide §2.3).

The TPU compiler is installed next to the CPU backend and compiles for a
chip that is DESCRIBED, not attached: what it refuses here (a slice off
the tiling, too much VMEM, a kernel that cannot be partitioned) it would
refuse on the machine, and finding that here costs no chip time. These
cases hold the pallas flash kernel — interpret mode everywhere else in
the suite — to the real Mosaic lowering at the widths the models use:
forward and backward, `gpt3_1p3b` head geometry (16 x 128) at seq
1024/2048/8192 (512 x 512 score tiles; s8192 bounds the VMEM they may
take), at 1280 (256 tiles) and 1100 (a padded tail, 128 tiles), and
BERT-base's (12 x 64) at the dispatch gate and causal. Nothing
runs, so they say nothing about results or times; `chip_smoke.py` does.

Plus the compile-cache placement rule, in a subprocess so jax's config is
as a fresh program finds it.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_topo():
    """A described v5e 2x2 host; the persistent compile cache is off
    around these compiles (an entry written for an unattached chip can
    never be read back, and warns on every later attempt)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_chip(v5e_topo):
    """One of its devices."""
    return jax.sharding.SingleDeviceSharding(v5e_topo.devices[0])


# (batch, seq, heads, head_dim), dtype, causal
FLASH_CASES = [
    ((2, 1024, 16, 128), jnp.bfloat16, True),
    ((2, 2048, 16, 128), jnp.bfloat16, True),
    ((1, 8192, 16, 128), jnp.bfloat16, True),
    ((2, 1024, 12, 64), jnp.bfloat16, False),
    ((1, 2048, 16, 128), jnp.float32, True),
    ((2, 1280, 16, 128), jnp.bfloat16, True),
    ((2, 1100, 16, 128), jnp.bfloat16, True),
    ((2, 2048, 12, 64), jnp.bfloat16, True),
]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize(
    "shape,dtype,causal", FLASH_CASES,
    ids=[f"b{s[0]}_s{s[1]}_h{s[2]}x{s[3]}_{jnp.dtype(d).name}"
         f"{'_causal' if c else ''}" for s, d, c in FLASH_CASES])
def test_flash_kernel_compiles_for_v5e(v5e_chip, shape, dtype, causal,
                                       direction):
    from paddle_tpu.kernels import flash_attention as fa

    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
    # as the chip compiles it, not at the -O0 conftest gives the CPU
    text = jax.jit(fn).lower(x, x, x).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    # the kernel itself, not an XLA rewrite: forward is one Mosaic call,
    # backward re-runs it for the residuals then the one backward kernel
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kernel_with_narrower_values_compiles_for_v5e(v5e_chip,
                                                            direction):
    """Latent attention's call shape at the new cell's size: 32 heads,
    q and k 192 wide (one contraction), v and the output 128, s 4,096
    (k and v whole in VMEM beside 512 x 512 score tiles)."""
    from paddle_tpu.kernels import flash_attention as fa

    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    qk = jax.ShapeDtypeStruct((1, 4096, 32, 192), jnp.bfloat16,
                              sharding=v5e_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=v5e_chip)
    text = jax.jit(fn).lower(qk, qk, v).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)


def test_fused_backward_fits_the_vmem_it_asks_for_at_the_joyai_cells_call(
        v5e_chip):
    """The JoyAI cell's own call, all of b4 x 32 heads: Q and dO whole,
    the float32 `dq` of a query head resident across its key blocks and
    the bf16 `dq` block beside it need more than the 16 MiB a Mosaic
    call gets unasked (at b1 the same widths fit, so the case above
    cannot see it). The kernel states its limit from its operands'
    shapes: a resident block that outgrows it fails here, not on the
    chip, and the limit stays far inside a v5e core's 128 MiB."""
    import re

    from paddle_tpu.kernels import flash_attention as fa

    def loss(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True).astype(
            jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    qk = jax.ShapeDtypeStruct((4, 4096, 32, 192), jnp.bfloat16,
                              sharding=v5e_chip)
    v = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16,
                             sharding=v5e_chip)
    (limit,) = re.findall(r"vmem_limit_bytes=(\d+)",
                          str(jax.make_jaxpr(grads)(qk, qk, v)))
    assert 16 * 2 ** 20 < int(limit) <= 32 * 2 ** 20
    text = jax.jit(grads).lower(qk, qk, v).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kernel_with_grouped_heads_compiles_for_v5e(v5e_chip,
                                                          direction):
    """LFM2's call shape at its cell's size: 32 query heads over 8
    key/value heads, 64 wide (half the lane axis), s 8,192 (a key/value
    head whole in VMEM beside 512 x 512 score tiles; the backward's
    grid over the group's four query heads, their float32 dk and dv
    resident whole). K and V enter the kernels at their 8 heads: no
    operand of a Mosaic call is a 32-head K."""
    from paddle_tpu.kernels import flash_attention as fa

    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=v5e_chip)
    text = jax.jit(fn).lower(q, kv, kv).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "= " in line]
    assert calls and all("bf16[32,8192,64]" in line
                         and "bf16[8,8192,64]" in line for line in calls)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kernel_with_wide_grouped_heads_compiles_for_v5e(v5e_chip,
                                                               direction):
    """Qwen3-Next's call at its cell's size: 16 query heads over 2
    key/value heads, 256 wide, b4 x 8,192 (the backward's float32 dk
    and dv of a key/value head resident across its group of eight)."""
    from paddle_tpu.kernels import flash_attention as fa

    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    q = jax.ShapeDtypeStruct((4, 8192, 16, 256), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((4, 8192, 2, 256), jnp.bfloat16,
                              sharding=v5e_chip)
    text = jax.jit(fn).lower(q, kv, kv).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)


def test_the_delta_rule_compiles_for_v5e_inside_its_room(v5e_chip):
    """Qwen3-Next's Gated DeltaNet rule as its cell calls it, forward and
    backward: b4 x 8,192, q and k at 16 key heads and v at 32 value
    heads of 128, bf16, q and k normalized inside the sweep. Each sweep
    forms a chunk's part a step, so what the pair needs beside its
    operands stays under 2 GiB (the whole-sequence form took 6.5 GB: the
    state, the optimizer's and the rest of a layer did not fit beside
    it). A chunk's T = (I + A)^-1 is matrix products, forward and
    backward: the program holds none of XLA:TPU's triangular inversions
    (`InvertDiagBlocksLowerTriangular`, which a triangular solve lowers
    to, ~0.34 ms a call on the chip)."""
    from paddle_tpu.nn.functional import delta_rule

    def loss(*args):
        return delta_rule.chunked_delta_rule(*args).astype(
            jnp.float32).sum()

    keys = jax.ShapeDtypeStruct((4, 8192, 16, 128), jnp.bfloat16,
                                sharding=v5e_chip)
    values = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16,
                                  sharding=v5e_chip)
    gate = jax.ShapeDtypeStruct((4, 8192, 32), jnp.float32,
                                sharding=v5e_chip)
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        keys, keys, values, gate, gate).compile(
        compiler_options={"xla_backend_optimization_level": 3})
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
    assert 'custom_call_target="InvertDiagBlocks' not in compiled.as_text()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_short_conv_kernel_compiles_for_v5e(v5e_chip, direction):
    """LFM2's operator at its cell's size: u [4, 8192, 3 x 2048] bf16,
    3 taps; 128 positions and all 6,144 channels a grid step, the
    backward with its two neighbour views and the taps' gradient block
    resident: inside the VMEM a kernel may take."""
    from paddle_tpu.kernels import short_conv as kernel

    u = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16,
                             sharding=v5e_chip)
    taps = jax.ShapeDtypeStruct((2048, 3), jnp.bfloat16, sharding=v5e_chip)
    dout = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16,
                                sharding=v5e_chip)
    assert kernel.supports(u.shape, taps.shape)
    if direction == "fwd":
        fn, args = kernel.short_conv, (u, taps)
    else:
        fn = lambda u, w, d: jax.vjp(kernel.short_conv, u, w)[1](d)  # noqa: E731
        args = (u, taps, dout)
    text = jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_silu_short_conv_kernel_compiles_for_v5e(v5e_chip, direction):
    """Gated DeltaNet's convolution at the Qwen3-Next cell's size: x [4,
    8192, 8192] bf16 (q, k and v side by side), 4 taps, through the SiLU
    form of the same kernels: inside the VMEM a kernel may take."""
    from paddle_tpu.kernels import short_conv as kernel

    x = jax.ShapeDtypeStruct((4, 8192, 8192), jnp.bfloat16,
                             sharding=v5e_chip)
    taps = jax.ShapeDtypeStruct((8192, 4), jnp.bfloat16, sharding=v5e_chip)
    assert kernel.supports(x.shape, taps.shape)
    if direction == "fwd":
        fn, args = kernel.silu_conv, (x, taps)
    else:
        fn = lambda x, w, d: jax.vjp(kernel.silu_conv, x, w)[1](d)  # noqa: E731
        args = (x, taps, x)
    text = jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    assert text.count("tpu_custom_call") >= 1


def _stage_for_v5e(fwd_bwd, args, chip, monkeypatch):
    """`to_static`'s own program of `fwd_bwd(*args)`, handed to the
    chip's compiler in place of the attached backend's: keep the
    function it would jit and the arguments of its first call, and
    compile that for the described chip as the chip compiles it."""
    import paddle_tpu as paddle

    held = {}

    class Staged(Exception):
        pass

    def keep(self, fun, **kwargs):
        def call(*args):
            held.update(fun=fun, kwargs=kwargs, args=args)
            raise Staged
        return call

    step = paddle.jit.to_static(fwd_bwd)
    monkeypatch.setattr(type(step), "_jit", keep)
    with pytest.raises(Staged):
        step(*args)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype, sharding=chip),
        held["args"])
    return jax.jit(held["fun"], **held["kwargs"]).lower(*shapes).compile(
        compiler_options={"xla_backend_optimization_level": 3})


@pytest.fixture(scope="module")
def held_experts_program(v5e_chip):
    """One routed-expert layer at the JoyAI cell's size (16,384 tokens,
    2,048 wide, 16 experts of 768 held of 256, 8 a token), forward and
    backward in bf16 through `to_static`, so that the scopes are
    entered as in a step: (compiled text, its temporaries' bytes)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import HeldExpertsLayer

    class Probe(paddle.nn.Layer):
        """The layer over rows that are a parameter, so that the rows'
        gradient is state the step keeps and not dead code."""

        def __init__(self):
            super().__init__()
            self.rows = self.create_parameter([4, 4096, 2048])
            self.moe = HeldExpertsLayer(2048, 768, 256, 8, ep_size=16,
                                        ep_rank=0, routed_scaling_factor=2.5)

        def forward(self):
            return self.moe(self.rows)

    probe = Probe()
    probe.to("bfloat16")

    def fwd_bwd():
        loss = probe().astype("float32").sum()
        loss.backward()
        return loss

    from paddle_tpu.kernels import put_rows

    with pytest.MonkeyPatch.context() as patch:
        # the chip's branch of the combine's gate
        patch.setattr(put_rows, "is_available", lambda: True)
        compiled = _stage_for_v5e(fwd_bwd, (), v5e_chip, patch)
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _grouped_products(text):
    """(instruction name, op_name) of every grouped-matmul call in the
    compiled text: the chip's own kernel, known by its tiling
    attribute whatever the compiler names it."""
    import re

    found = []
    for line in text.splitlines():
        if "ragged_dot_tiling=" in line:
            op_name = re.search(r'op_name="([^"]*)"', line)
            found.append((line.split("=")[0].strip().lstrip("%"),
                          op_name.group(1) if op_name else ""))
    return found


def test_held_experts_layer_compiles_for_v5e(held_experts_program):
    """The grouped products stay the chip's own grouped-matmul calls,
    inside loops with a run-time trip count and under no conditional:
    three a block forward; backward two again, one for the hidden
    gradient, two for the rows' and three a weight over the staged
    rows, written once and once more inside the loop over further
    staged blocks."""
    text, _temp = held_experts_program
    assert len(_grouped_products(text)) >= 9
    # forward, the backward's blocks, the further staged blocks and
    # their blocks
    assert text.count(" while(") >= 4 and "conditional(" not in text
    # no grouped product fell back to a dense product over every group
    assert "activations_broadcast_fusion" not in text


def test_held_experts_device_time_keeps_its_names_for_v5e(
        held_experts_program):
    """What keeps `moe.experts_roofline` and `moe.route_share` honest:
    every grouped product, forward, replayed and backward, is either
    called `ragged-dot-none*` (the reader takes those by name) or
    carries the scope `experts`; the row gathers carry `dispatch`, or
    `combine` where they put a block's results in token order or take
    the sum's rows they add to, and the gates' gradient's scatter-add
    `dispatch`, in the backward (`transpose(` in the name) as in the
    forward. The sums y and dx take their rows by the Mosaic kernel
    `put_rows` under `combine`, one call a block each way: no scatter
    of [*, 2048] rows is left in the block loops."""
    import re

    text, _temp = held_experts_program
    products = _grouped_products(text)
    assert all(name.startswith("ragged-dot-none") or "pt.experts" in op
               for name, op in products), products
    found = [(line, re.search(r'op_name="([^"]*)"', line).group(1))
             for line in text.splitlines()
             if re.search(r'op_name="[^"]*/(?:gather|scatter-add)"', line)
             or "tpu_custom_call" in line and 'op_name="' in line]
    loops = [(line, op) for line, op in found if "/while/body/" in op]
    assert loops and all("pt.held_experts_ffn" in op for _line, op in loops)
    for backward in (False, True):
        mine = [(line, op) for line, op in loops
                if ("transpose(" in op) == backward]
        gathers = [op for _line, op in mine if op.endswith("/gather")]
        assert any("pt.dispatch/" in op for op in gathers), mine
        assert any("pt.combine/" in op for op in gathers), mine
        assert all("pt.dispatch/" in op or "pt.combine/" in op
                   for op in gathers), mine
        kernels = [op for line, op in mine if "tpu_custom_call" in line]
        assert kernels and all("pt.combine/" in op for op in kernels), mine
        # the scatters themselves, not the fusions named after them
        scatters = [(line, op) for line, op in mine
                    if op.endswith("/scatter-add") and " scatter(" in line]
        assert not [op for line, op in scatters
                    if ",2048]" in line.split("=")[1]], scatters
        assert all("pt.dispatch/" in op for _line, op in scatters), scatters
        assert bool(scatters) == backward, scatters


# `held_experts_program`'s temporaries at the parent commit (00c0e96:
# four chunks of 32,768 slots under `lax.cond`, each a remat region)
PARENT_HELD_EXPERTS_TEMP_BYTES = 2_130_259_456
# ... and of the block loop whose scatter-adds took repeating indices
BLOCK_LOOP_TEMP_BYTES = 1_222_008_832


def test_held_experts_layer_keeps_less_than_the_chunked_loop_for_v5e(
        held_experts_program):
    """The token order of every block (int32 [T * top_k] twice a layer
    application) and a block's rows put in it add a few MB to the
    loop's temporaries, not a second [T, D] sum."""
    _text, temp = held_experts_program
    assert temp < PARENT_HELD_EXPERTS_TEMP_BYTES
    assert temp <= BLOCK_LOOP_TEMP_BYTES + 64 * 2 ** 20


def test_decoder_block_evaluates_gelus_erfc_once_for_v5e(v5e_chip,
                                                         monkeypatch):
    """A GPT block at the cells' widths (h 2048, FFN 8192), forward and
    backward under bf16 autocast, compiled for the chip: the erfc
    polynomial of the exact GELU (an `exponential` and a `divide` pair
    of its own, over `[.., 8192]`) is in the program once, and the
    derivative's `exp(-x^2/2)` beside it. Left to itself XLA:TPU stores
    no GELU and recomputes the polynomial in fc2's forward, its weight
    gradient and its input gradient (4 `exponential`, 6 `divide`):
    `F.gelu` keeps the factor behind a barrier, and this case fails the
    day the compiler duplicates through it."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.models.decoder import DecoderBlock
    from paddle_tpu.models.gpt import GPTConfig

    block = DecoderBlock(GPTConfig(
        vocab_size=128, hidden_size=2048, num_layers=1, num_heads=16,
        intermediate_size=8192, max_seq_len=512, hidden_dropout=0.0,
        attention_dropout=0.0))
    block.to("bfloat16")

    def fwd_bwd(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            loss = block(x).sum()
        loss.backward()
        return loss

    x = paddle.to_tensor(jnp.zeros((2, 512, 2048), jnp.bfloat16),
                         stop_gradient=False)
    text = _stage_for_v5e(fwd_bwd, (x,), v5e_chip, monkeypatch).as_text()
    ffn_wide = {op: len(re.findall(
        r"= \w+\[[\d,]*8192\]\S* %s\(" % op, text))
        for op in ("exponential", "divide")}
    assert ffn_wide == {"exponential": 2, "divide": 2}


@pytest.mark.parametrize("grad_dtype", ["bfloat16", "float32"])
def test_zero_bucket_crosses_the_v5e_links_in_its_gradients_dtype(
        v5e_topo, grad_dtype):
    """One ZeRO bucket's reduction (`Optimizer._zero_reduced_shard`, a
    2048 x 1024 bf16 weight and its bias over dp=4) compiled for the
    four described chips. bf16 gradients: ONE all-to-all whose operand
    is the bf16 bucket, split four ways, and neither an all-reduce nor a
    reduce-scatter — the float32 sum is four converts and three adds
    after it. float32 gradients: the float32 reduction XLA:TPU makes of
    `psum_scatter` (an all-reduce inside a `kCustom` fusion), and no
    all-to-all."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import parallel_env

    dp = 4
    tpu_mesh = Mesh(np.array(v5e_topo.devices).reshape(dp), ("dp",))
    # the stores are placed on attached devices; only the lowering sees
    # the described ones
    host_mesh = parallel_env.make_mesh({"dp": dp})
    layer = nn.Linear(2048, 1024)
    layer.to("bfloat16")
    opt = paddle.optimizer.AdamW(parameters=layer.parameters(),
                                 learning_rate=1e-4, multi_precision=True)
    opt._zero_enable(axis="dp", stage=1, mesh=host_mesh)
    (zb,) = opt._zero["buckets"]

    def body(*grads):
        with parallel_env.dp_axis_ctx("dp"):
            for p, g in zip(zb.params, grads):
                p._grad = g
            shard, _present = opt._zero_reduced_shard(zb, "dp", dp, True,
                                                      True)
        for p in zb.params:
            p._grad = None
        return shard

    fn = jax.shard_map(body, mesh=tpu_mesh, in_specs=P(),
                       out_specs=P("dp"), check_vma=False)
    shapes = [jax.ShapeDtypeStruct(shape, jnp.dtype(grad_dtype),
                                   sharding=NamedSharding(tpu_mesh, P()))
              for shape in zb.shapes]
    text = jax.jit(fn).lower(*shapes).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
    pieces = r"= bf16\[%d,%d,1024\]\S* all-to-all\(" % (dp, zb.shard_rows)
    if grad_dtype == "bfloat16":
        assert len(re.findall(pieces, text)) == 1
        assert "all-reduce" not in text and "reduce-scatter" not in text
    else:
        assert "all-to-all" not in text
        assert re.search(r"= f32\[\d+,1024\]\S* all-reduce\(", text)


_PLACEMENT_PROBE = """
import json
import jax
from paddle_tpu.jit import compile_cache

set_in_code = []
update = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        set_in_code.append(value)
    return update(name, value)
jax.config.update = spy
before = jax.config.jax_compilation_cache_dir
compile_cache.enable()
print(json.dumps({"before": before, "set_in_code": set_in_code,
                  "after": jax.config.jax_compilation_cache_dir,
                  "cache_dir": compile_cache.cache_dir()}))
"""


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_places_it", "fixed_in_checkout"])
def test_compile_cache_placement(placed, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> jax's own setting stands and
    nothing sets a directory in code; unset -> the fixed path inside the
    checkout (never the home directory, a temp name, a pid or a time)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _PLACEMENT_PROBE],
                       capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    if placed:
        assert got["set_in_code"] == []
        assert got["before"] == got["after"] == got["cache_dir"] \
            == str(tmp_path)
    else:
        fixed = os.path.join(REPO, ".jax_cache")
        assert got["before"] is None
        assert got["set_in_code"] == [fixed]
        assert got["after"] == got["cache_dir"] == fixed
