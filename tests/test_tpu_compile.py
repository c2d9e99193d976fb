"""AOT compiles for a DESCRIBED TPU v5e (2x2), no chip attached.

The chip's compiler is installed in the sandbox and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2). These
tests guard what interpret-mode tests cannot see: a kernel the compiler
refuses (VMEM, tiling alignment), a step that no longer carries its kernel
(``tpu_custom_call`` absent — the lowering silently took the interpreter or
the XLA fallback), a program over the chip's memory. A compile that passes is
not a chip run; ``chip_smoke.py`` is.

Rules this file keeps (same guide): the topology is described inside a
module-scoped fixture that skips when it cannot be, never at import, never in
``skipif``/``parametrize``; shardings and shapes are built in fixtures or in
the test; everything compiles in the test's own process (the worker that
loaded libtpu keeps its lock); ONE file, so one xdist worker owns the library.
The persistent compile cache is off for the whole suite (tests/conftest.py):
an entry compiled for a described device cannot be read back without a chip.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from flexible_llm_sharding_tpu.config import LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import pallas_attention as pa
from flexible_llm_sharding_tpu.runtime import decode, executor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.array(topo.devices), ("tp",))


@pytest.fixture
def lowering_sees_tpu(monkeypatch):
    """ops/pallas_attention.py picks interpret mode from
    ``jax.default_backend()``, which is the CPU here; on the chip it is the
    TPU. Steer it in the test, not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled, compiled.as_text()


# (n_q, n_kv, qk head dim, v head dim): Llama-3-8B, Llama-3-70B, MLA
# (DeepSeek-V2/V3, Moonlight: qk 128+64 rope, v 128; kanana-2 the same at 32
# heads), MiMo-V2-Flash's full and window layers (qk 192, v 128, no MLA),
# Ouro-2.6B (plain multi-head) and MiniCPM-SALA's softmax layers (16:1).
WIDTHS = {
    "ouro": (16, 16, 128, 128),
    "minicpm_sala": (32, 2, 128, 128),
    "llama3_8b": (32, 8, 128, 128),
    "llama3_70b": (64, 8, 128, 128),
    "mla": (16, 16, 192, 128),
    "kanana2_mla": (32, 32, 192, 128),
    "mimo_full": (64, 4, 192, 128),
    "mimo_window": (64, 8, 192, 128),
}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("length", [512, 3392, 4096])
def test_causal_kernel_compiles(one_chip, widths, length):
    """512 and 4096 are whole tiles; 3392 (the long cells' largest bucket) is
    zero-padded to 14 query tiles of 256 and 7 key tiles of 512 inside the
    wrapper. K and V of a KV head stay whole in VMEM beside the 256 x 512
    score tile: an overrun shows here, not first on the chip."""
    n_q, n_kv, hd, dv = WIDTHS[widths]
    s = functools.partial(_sds, one_chip)
    fn = functools.partial(pa.flash_causal_attention, interpret=False)
    _, text = _compile(
        jax.jit(fn),
        s((length, n_q, hd)), s((length, n_kv, hd)), s((length, n_kv, dv)),
        s((), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("lp", [3392, 4096])
def test_prefix_shared_kernel_compiles(one_chip, widths, lp):
    n_q, n_kv, hd, dv = WIDTHS[widths]
    s = functools.partial(_sds, one_chip)
    ns, ls = 4, 64
    fn = functools.partial(pa.flash_prefix_shared_attention, interpret=False)
    _, text = _compile(
        jax.jit(fn),
        s((ns, ls, n_q, hd)), s((lp, n_kv, hd)), s((lp, n_kv, dv)),
        s((ns, ls, n_kv, hd)), s((ns, ls, n_kv, dv)), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("widths", ["llama3_8b", "llama3_70b"])
def test_decode_kernel_compiles(one_chip, widths):
    # MLA decodes through the XLA op (models/llama.py: kv_lora_rank), so the
    # decode kernel has no MLA shape to guard.
    n_q, n_kv, hd, _ = WIDTHS[widths]
    s = functools.partial(_sds, one_chip)
    lp, ns, ls, t = 4096, 4, 64, 64
    fn = functools.partial(pa.flash_decode_attention, interpret=False)
    _, text = _compile(
        jax.jit(fn),
        s((ns, 1, n_q, hd)), s((lp, n_kv, hd)), s((lp, n_kv, hd)),
        s((ns, ls, n_kv, hd)), s((ns, ls, n_kv, hd)),
        s((ns, t, n_kv, hd)), s((ns, t, n_kv, hd)),
        s((), jnp.int32), s((ns,), jnp.int32), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text


# --- MiMo-V2-Flash's window layers: the sink, and the whole step ------------

def test_kernels_with_sink_and_window_compile(one_chip):
    """The sink rides a second scalar-prefetch operand (float32 [n_q]) into
    all three kernels; the decode kernel also carries V's own head dim."""
    n_q, n_kv, hd, dv = WIDTHS["mimo_window"]
    s = functools.partial(_sds, one_chip)
    lp, ns, ls, t = 4096, 4, 64, 64
    kw = dict(interpret=False, window=128)
    sink = s((n_q,), jnp.float32)
    for fn, args in (
        (pa.flash_causal_attention,
         (s((lp, n_q, hd)), s((lp, n_kv, hd)), s((lp, n_kv, dv)), s((), jnp.int32))),
        (pa.flash_prefix_shared_attention,
         (s((ns, ls, n_q, hd)), s((lp, n_kv, hd)), s((lp, n_kv, dv)),
          s((ns, ls, n_kv, hd)), s((ns, ls, n_kv, dv)), s((), jnp.int32))),
        (pa.flash_decode_attention,
         (s((ns, 1, n_q, 256)), s((lp, n_kv, 256)), s((lp, n_kv, dv)),
          s((ns, ls, n_kv, 256)), s((ns, ls, n_kv, dv)),
          s((ns, t, n_kv, 256)), s((ns, t, n_kv, dv)),
          s((), jnp.int32), s((ns,), jnp.int32), s((), jnp.int32))),
    ):
        wrapped = jax.jit(lambda sk, *a, fn=fn: fn(*a, sink=sk, **kw))
        _, text = _compile(wrapped, sink, *args)
        assert "tpu_custom_call" in text, fn.__name__


def _mimo_cfg():
    import json

    from benchmark.families.mimo_v2_flash import weights

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        model = json.load(f)
    model.pop("rehearsal")
    return LlamaConfig.from_hf_config(weights.hf_config(model))


@pytest.mark.parametrize("layer", [1, 5])
@pytest.mark.parametrize("length", [3392, 4096])
def test_mimo_decoder_block_at_the_cell_s_shapes(one_chip, lowering_sees_tpu, layer, length):
    """One expert layer of each attention kind (layer 1 window, layer 5 full)
    as the benchmark's cell runs it: published widths, 16 of 256 experts
    held, the cell's longest prefix bucket (3392: the flash kernels pad it
    to their tiles) and the longest the path admits (4096), one prompt a
    block, the expert counts out. Both kernels in the program, and the step
    beside four 1 GB shards in flight and ~11 GB of pins inside the chip."""
    cfg = _mimo_cfg()
    sliding = cfg.layer_sliding[layer]
    shapes = jax.eval_shape(
        lambda: llama.init_mixed_params(
            jax.random.PRNGKey(0), dataclasses.replace(
                cfg, num_hidden_layers=layer + 1,
                layer_sliding=cfg.layer_sliding[: layer + 1],
                moe_layer_pattern=cfg.moe_layer_pattern[: layer + 1]),
            dtype=BF16)
    )["layers"][layer]
    assert ("sink" in shapes["attn"]) == sliding
    assert shapes["mlp"]["gate"].shape == (16, 4096, 2048)
    s = functools.partial(_sds, one_chip)
    seg = {
        "layers": jax.tree.map(lambda x: s((1, *x.shape), x.dtype), shapes),
        "sliding": s((1,), jnp.bool_), "rope": None,
    }
    compiled, text = _compile(
        executor._decoder_block,
        cfg, seg, s((1, length, 4096)), s((1, 4, 64, 4096)), s((1,), jnp.int32), True,
        None, None, True,
    )
    assert text.count("tpu_custom_call") >= 2
    # The routed experts are grouped matmuls (the Pallas kernel) over the
    # 8 x 3648 sorted assignments, of which the held 16 experts' groups are
    # visited.
    assert text.count("%grouped_matmul") >= 3 and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9


# --- the sigmoid-router expert layer as grouped matmuls ---------------------

# (configuration file, prompts a block, use_pallas)
EXPERT_BLOCKS = {
    "moonlight_b8": ("moonlight-16b-a3b", 2, True),   # score-b8: blocks of 1-2 prompts
    "moonlight_b32": ("moonlight-16b-a3b", 6, True),  # score-b32: blocks of 3-6
    "kanana_b8": ("kanana-2-30b-a3b", 2, True),
    "kanana_b8_xla": ("kanana-2-30b-a3b", 2, False),  # the fallback
}


@pytest.mark.parametrize("block", sorted(EXPERT_BLOCKS))
def test_deepseek_decoder_block_runs_experts_as_grouped_matmuls(
    one_chip, lowering_sees_tpu, block
):
    """One expert layer at published widths over a block of the cells'
    longest bucket (prefix 768, 4 suffixes of 64): the three expert
    projections compile to grouped matmuls over the block's rows sorted by
    expert (the Pallas kernel ``grouped_matmul`` with ``use_pallas``, XLA's own
    ``ragged-dot`` custom call without), and the step's temporaries stay
    under ONE [rows, experts, width] bfloat16 array, of which the
    compute-all einsums held two to three."""
    import json

    from benchmark import weights

    name, prompts, use_pallas = EXPERT_BLOCKS[block]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        model = json.load(f)
    model.pop("rehearsal")
    cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
    shapes = jax.eval_shape(
        lambda: llama.init_mixed_params(
            jax.random.PRNGKey(0), dataclasses.replace(
                cfg, num_hidden_layers=2, moe_layer_pattern=cfg.moe_layer_pattern[:2]),
            dtype=BF16)
    )["layers"][1]
    e, d, f = shapes["mlp"]["gate"].shape
    assert e == cfg.num_local_experts and "correction_bias" in shapes["mlp"]
    s = functools.partial(_sds, one_chip)
    seg = {
        "layers": jax.tree.map(lambda x: s((1, *x.shape), x.dtype), shapes),
        "sliding": None, "rope": None,
    }
    lp, ns, ls = 768, 4, 64
    rows = prompts * (lp + ns * ls)
    compiled, text = _compile(
        executor._decoder_block,
        cfg, seg, s((prompts, lp, d)), s((prompts, ns, ls, d)),
        s((prompts,), jnp.int32), use_pallas,
    )
    assert text.count("%grouped_matmul" if use_pallas else "%ragged-dot") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < rows * e * f * 2


# --- whole steps at Llama-3-8B widths (chip_smoke.py's shapes) -------------

LLAMA3_8B = LlamaConfig(
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=8,
    num_attention_heads=32,
    num_key_value_heads=8,
    rope_theta=500000.0,
    max_position_embeddings=8192,
)
K_LAYERS, B, LP, S, LS, T = 2, 8, 320, 4, 64, 8


def _layer_shapes():
    return jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), LLAMA3_8B, dtype=BF16)
    )["layers"][0]


def _segment(sharding):
    """Shapes of K_LAYERS stacked decoder layers on one placement."""
    stacked = jax.tree.map(
        lambda x: _sds(sharding, (K_LAYERS, *x.shape), x.dtype), _layer_shapes()
    )
    return {"layers": stacked, "sliding": None, "rope": None}


def test_decoder_block_carries_kernels(one_chip, lowering_sees_tpu):
    """The scoring step as the executor jits it, use_pallas on: both flash
    kernels must be IN the compiled program. Without the steer the lowering
    sees the CPU, emits the interpreter's loops and compiles just as
    happily with zero custom calls — the failure this test exists for."""
    s = functools.partial(_sds, one_chip)
    compiled, text = _compile(
        executor._decoder_block,
        LLAMA3_8B, _segment(one_chip),
        s((B, LP, 4096)), s((B, S, LS, 4096)), s((B,), jnp.int32), True,
    )
    assert text.count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _kv_shapes(s, n_kv, hd=128):
    return {
        "kp": s((K_LAYERS, B, LP, n_kv, hd)), "vp": s((K_LAYERS, B, LP, n_kv, hd)),
        "ks": s((K_LAYERS, B, S, LS, n_kv, hd)), "vs": s((K_LAYERS, B, S, LS, n_kv, hd)),
        "kg": s((K_LAYERS, B, S, T, n_kv, hd)), "vg": s((K_LAYERS, B, S, T, n_kv, hd)),
    }


def test_kv_decode_step_carries_kernel(one_chip, lowering_sees_tpu):
    s = functools.partial(_sds, one_chip)
    _, text = _compile(
        decode._decode_decoders,
        LLAMA3_8B, True, None, _segment(one_chip), _kv_shapes(s, 8),
        s((B, S, 1, 4096)), s((B,), jnp.int32), s((B, S), jnp.int32),
        s((), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_prefill_step_carries_kernels(one_chip, lowering_sees_tpu):
    s = functools.partial(_sds, one_chip)
    _, text = _compile(
        decode._prefill_decoders,
        LLAMA3_8B, True, None, _segment(one_chip),
        s((B, LP, 4096)), s((B, S, LS, 4096)), s((B,), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 2


def test_tp4_shard_map_kernel_compiles(tp_mesh, lowering_sees_tpu):
    """The prefix-shared kernel per head-shard under a 4-chip tp mesh
    (models/llama.py _flash_tp_prefix_shared): pallas_call has no GSPMD rule,
    so this shard_map is what tensor parallelism runs."""
    rep = NamedSharding(tp_mesh, P())
    s = functools.partial(_sds, rep)
    fn = jax.jit(
        lambda *a: llama._flash_tp_prefix_shared(tp_mesh, *a, None, {})
    )
    _, text = _compile(
        fn,
        s((S, LS, 32, 128)), s((LP, 8, 128)), s((LP, 8, 128)),
        s((S, LS, 8, 128)), s((S, LS, 8, 128)), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_tp4_decoder_block_carries_kernels(tp_mesh, lowering_sees_tpu):
    """The scoring step under --tensor_parallel 4: weights sharded by the
    placement's own specs, activations replicated, kernels inside."""
    from flexible_llm_sharding_tpu.parallel.sharding import layer_specs

    stacked = jax.tree.map(
        lambda x, spec: _sds(
            NamedSharding(tp_mesh, P(None, *spec)), (K_LAYERS, *x.shape), x.dtype
        ),
        _layer_shapes(), layer_specs("tp", LLAMA3_8B),
    )
    rep = NamedSharding(tp_mesh, P())
    s = functools.partial(_sds, rep)
    compiled, text = _compile(
        executor._decoder_block,
        LLAMA3_8B, {"layers": stacked, "sliding": None, "rope": None},
        s((B, LP, 4096)), s((B, S, LS, 4096)), s((B,), jnp.int32), True, tp_mesh,
    )
    assert text.count("tpu_custom_call") >= 2
    assert "all-reduce" in text  # the row-parallel products' ICI reduction


# --- MiniCPM-SALA: the lightning kernel and both layer kinds' steps ------------

@pytest.mark.parametrize("n,length", [(1, 3392), (1, 4096), (4, 64)])
def test_lightning_kernel_compiles(one_chip, n, length):
    """The cell's calls: a prefix alone (3392 rows: thirteen 256-row chunks
    and a ragged one), the longest bucket, four suffixes from one state."""
    from flexible_llm_sharding_tpu.ops import lightning_attention as la

    s = functools.partial(_sds, one_chip)
    f = jax.jit(functools.partial(la.lightning_attention, interpret=False))
    _, text = _compile(
        f, s((n, length, 32, 128)), s((n, length, 32, 128)), s((n, length, 32, 128)),
        s((length, 32), jnp.float32), s((32, 128, 128), jnp.float32),
    )
    assert "tpu_custom_call" in text and "lightning_attention" in text


def _sala_cfg():
    import json

    from benchmark.families.minicpm_sala import weights

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "minicpm-sala.json")) as f:
        model = json.load(f)
    model.pop("rehearsal")
    return LlamaConfig.from_hf_config(weights.hf_config(model))


@pytest.mark.parametrize("linear,length", [(True, 3392), (False, 3392), (False, 4096)])
def test_sala_decoder_block_at_the_cell_s_shapes(one_chip, lowering_sees_tpu, linear, length):
    """A run of three linear layers and one softmax layer as the benchmark's
    cell runs them: published widths, the longest prefix bucket, one prompt a
    block. The linear run carries the lightning kernel (a prefix call and a
    suffix call) and no flash kernel; the softmax layer the two flash kernels
    at 32 query heads over 2 KV heads, also at the longest bucket the path
    admits (4096). Both beside ~11 GB of pins and four 0.57 GB shards in
    flight inside the chip."""
    cfg = _sala_cfg()
    k = 3 if linear else 1
    shapes = jax.eval_shape(
        lambda: llama.init_layer_params(jax.random.PRNGKey(0), cfg, BF16, linear=linear)
    )
    assert ("o_norm" in shapes["attn"]) == linear and "wg" in shapes["attn"]
    assert shapes["attn"]["wk"].shape == (4096, 4096 if linear else 256)
    s = functools.partial(_sds, one_chip)
    seg = {
        "layers": jax.tree.map(lambda x: s((k, *x.shape), x.dtype), shapes),
        "sliding": None, "rope": s((k,), jnp.bool_), "index": s((k,), jnp.int32),
    }
    compiled, text = _compile(
        executor._decoder_block,
        cfg, seg, s((1, length, 4096)), s((1, 4, 64, 4096)), s((1,), jnp.int32), True,
    )
    assert ("lightning_attention" in text) == linear
    assert ("flash_causal_attention" in text) != linear
    assert text.count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _ouro_cfg():
    import json

    from benchmark.families.ouro import weights

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "ouro-2.6b.json")) as f:
        model = json.load(f)
    model.pop("rehearsal")
    return LlamaConfig.from_hf_config(weights.hf_config(model))


@pytest.mark.parametrize(
    "step,length",
    [("decoder", 3392), ("decoder", 4096), ("loop_norm", 3392), ("exit", 3392)],
)
def test_ouro_steps_at_the_cell_s_shapes(one_chip, lowering_sees_tpu, step, length):
    """What a looped model's batch dispatches, at Ouro-2.6B's published widths
    and the cell's longest bucket, one prompt a block: a decoder layer with
    its four norms and the two flash kernels at 16 heads over 16 KV heads of
    128 (also at 4096, the longest bucket the path admits); the step end before the last (the final norm over every row and the
    gate on the scored rows: one program for every step, the step is traced);
    the last step's exit."""
    cfg = _ouro_cfg()
    assert cfg.total_ut_steps == 4 and cfg.ffw_sandwich_norms and not cfg.norm_unit_offset
    s = functools.partial(_sds, one_chip)
    prefix, suffix = s((1, length, 2048)), s((1, 4, 64, 2048))
    norm = {"scale": s((2048,)), "gate": {"kernel": s((2048, 1)), "bias": s((1,))}}
    state = (s((1, 4), jnp.float32),) * 3 + (s((1, 4, 1, 2048)),)
    if step == "decoder":
        shapes = jax.eval_shape(
            lambda: llama.init_layer_params(jax.random.PRNGKey(0), cfg, BF16)
        )
        assert "post_feedforward_layernorm" in shapes and shapes["attn"]["wk"].shape == (2048, 2048)
        seg = {"layers": jax.tree.map(lambda x: s((1, *x.shape), x.dtype), shapes),
               "sliding": None, "rope": None}
        compiled, text = _compile(
            executor._decoder_block, cfg, seg, prefix, suffix, s((1,), jnp.int32), True)
        assert "flash_causal_attention" in text and "flash_prefix_shared_attention" in text
        assert text.count("tpu_custom_call") >= 2
    elif step == "loop_norm":
        compiled, text = _compile(
            executor._loop_norm_block, cfg, norm, prefix, suffix, s((1, 4), jnp.int32),
            state, s((), jnp.int32))
    else:
        compiled, text = _compile(
            executor._exit_block, cfg, norm, s((1, 4, 1, 2048)), state, s((1, 4), jnp.bool_))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# --- GLM-5.3-Flash: the KDA kernel and the three layer kinds' steps -----------

@pytest.mark.parametrize("n,length", [(1, 1856), (4, 64)])
def test_kda_kernel_compiles(one_chip, n, length):
    """The cell's calls: the longest prefix bucket alone (29 chunks of 64
    rows, the float32 solve inside), four suffixes from one state."""
    from flexible_llm_sharding_tpu.ops import kda_attention as ka

    s = functools.partial(_sds, one_chip)
    f = jax.jit(functools.partial(ka.kda_attention, interpret=False))
    _, text = _compile(
        f, s((n, length, 64, 128)), s((n, length, 64, 128)), s((n, length, 64, 128)),
        s((n, length, 64, 128), jnp.float32), s((n, length, 64), jnp.float32),
        s((64, 128, 128), jnp.float32),
    )
    assert "tpu_custom_call" in text and "kda_chunk" in text


def _glm_model():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "glm-5.3-flash.json")) as f:
        model = json.load(f)
    model.pop("rehearsal")
    return model


@pytest.mark.parametrize("layer,kind", [(0, "kda_dense"), (3, "latent_moe"), (4, "kda_moe")])
def test_glm_decoder_block_at_the_cell_s_shapes(one_chip, lowering_sees_tpu, layer, kind):
    """One layer of each kind as the benchmark's cell runs it: published
    widths, a row 4 x 4096 wide between layers, the longest prefix bucket, one
    prompt a block. A KDA layer carries the KDA kernel (a prefix call and a
    suffix call) and no flash kernel, a latent layer the two flash kernels at
    64 heads of 256 / 256 with no rotary; an expert layer the grouped matmuls
    over 36 held experts of 288 routed. Beside the tier's pins and four 2.14 GB
    shards in flight inside the chip."""
    from benchmark.families.glm5_next_text import weights

    model = _glm_model()
    cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
    assert weights.layer_kind(model, layer) == kind and cfg.hc_mult == 4
    s = functools.partial(_sds, one_chip)
    seg = {
        "layers": weights.unflatten({
            k: s((1, *shape)) for k, shape, _ in weights.tensor_specs(model, f"model.layers.{layer}")
        }),
        "sliding": None, "rope": None, "index": s((1,), jnp.int32),
    }
    compiled, text = _compile(
        executor._decoder_block,
        cfg, seg, s((1, 1856, 16384)), s((1, 4, 64, 16384)), s((1,), jnp.int32), True,
        None, None, True,
    )
    # By the kernels' own op lines: the text's table of Python function names
    # outlives a compilation, so a bare name may be an earlier program's.
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert any("kda_chunk" in c for c in calls) == kind.startswith("kda")
    assert any("flash_causal_attention" in c for c in calls) == kind.startswith("latent")
    assert len(calls) >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
