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
    # backward re-runs it for the residuals then the dq and dkv kernels
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)


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
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)


def test_held_experts_layer_compiles_for_v5e(v5e_chip):
    """The routed experts at the new cell's widths (h 2,048, experts of
    768, 16 held of 256, 8 a token), forward and backward: the grouped
    products stay the chip's own grouped-matmul calls inside the chunk
    loop's conditional, three forward and their transposes."""
    from paddle_tpu.parallel.moe import held_experts_ffn

    def loss(x, router, bias, gate, up, down):
        y, _pairs, _load = held_experts_ffn(
            x, router, bias, gate, up, down, top_k=8, first_expert=0,
            scale=2.5)
        return y.astype(jnp.float32).sum()

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        shape(4096, 2048), shape(2048, 256), shape(256, dtype=jnp.float32),
        shape(16, 2048, 768), shape(16, 2048, 768),
        shape(16, 768, 2048)).compile().as_text()
    assert text.count("ragged-dot") >= 9 and "conditional(" in text


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

    # to_static's own program, handed to the chip's compiler in place of
    # the attached backend's: keep the function it would jit and the
    # arguments of its first call
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
    x = paddle.to_tensor(jnp.zeros((2, 512, 2048), jnp.bfloat16),
                         stop_gradient=False)
    with pytest.raises(Staged):
        step(x)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                       sharding=v5e_chip), held["args"])
    text = jax.jit(held["fun"], **held["kwargs"]).lower(*shapes).compile(
        compiler_options={"xla_backend_optimization_level": 3}).as_text()
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
