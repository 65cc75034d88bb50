"""The BHTD attention kernels, the experts' grouped-matmul kernels, the
gated delta rule's kernels and the causal convolution's in front of it
compile for a TPU v5e at the shapes the chip runs them at, on this
CPU-only machine (one file for all: the worker that is handed it is
the one that loads libtpu): the TPU's compiler is
installed and compiles for a chip that is described, not attached
(.claude/skills/verify/SKILL.md, "Compile for the chip without a chip").
What Mosaic refuses (a tile over its scoped VMEM, a slice off the tiling)
shows here at no chip time; nothing runs, so this says nothing about
results or times. The topology is described inside a fixture: only the
worker that is handed this file loads libtpu.
"""

import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import causal_conv as cc
from paddle_tpu.parallel import embed_grad as eg
from paddle_tpu.parallel import flash_attention as fa
from paddle_tpu.parallel import gated_delta_rule as gdr
from paddle_tpu.parallel import grouped_matmul as gm
from paddle_tpu.parallel import pair_sum as ps
from paddle_tpu.parallel import rope
from paddle_tpu.parallel import selective_scan as ss


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def real_kernels(monkeypatch):
    """The dispatch takes the Pallas path (this process's backend is the
    CPU) and lowers it through Mosaic, not the interpreter; as on the
    chip, without 64-bit types (conftest.py turns them on for the CPU
    suite and Mosaic takes none) and without the persistent compile
    cache (an executable for a described chip cannot be read back here:
    the next run would warn and compile again)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fa, "kernels_enabled", lambda: True)
    monkeypatch.setattr(fa, "_INTERPRET", False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


# The Mosaic calls of a BHTD forward and backward, by what
# ``fa.bhtd_bwd_form`` answers for the call.
_NAMES = {
    "fused": ("attn.bhtd.fwd", "attn.bhtd.bwd"),
    "split": ("attn.bhtd.fwd", "attn.bhtd.bwd_dq", "attn.bhtd.bwd_dkv"),
}


def _calls(text):
    """The BHTD attention kernels an HLO text calls, by the name their
    op_name carries (".../attn.bhtd.bwd/pallas_call", under a grad
    ".../transpose(jvp(attn.bhtd.bwd))/pallas_call")."""
    return set(re.findall(r"[/(](attn\.bhtd\.\w+)[)/]", text))


def _holds_the_calls(text, form):
    # one form or the other, never both
    assert _calls(text) == set(_NAMES[form])


# (b, h, t, dh, dtype, bias shape or None, causal, p_drop, the caller's
# q_block or None, the tile); the backward of the calls that keep one
# head a step, draw no dropout mask and cut lse and delta from rows is
# ONE call (attn.bhtd.bwd), of the others the pair
_CASES = {
    "olmoe": (2, 16, 4096, 128, jnp.bfloat16, None, True, 0.0, None,
              (1, 512, 512)),
    "olmoe_row_bias": (1, 16, 4096, 128, jnp.bfloat16, (1, 1, 4096, 4096),
                       True, 0.0, None, (1, 512, 512)),
    "chip_smoke_bhtd": (2, 8, 4096, 64, jnp.bfloat16, (2, 1, 1, 4096), True,
                        0.0, None, (1, 512, 512)),
    "head_groups_dropout": (1, 16, 1024, 128, jnp.float32, None, False, 0.3,
                            None, (1, 512, 512)),
    "all_heads_in_a_step": (8, 2, 1024, 64, jnp.bfloat16, (8, 2, 1024, 1024),
                            True, 0.1, None, (2, 256, 256)),
    # 512 does not divide t: a ring's quarter of 3072 at OLMoE's heads,
    # 12 heads of 64 at the score cap with a bias of every row and head
    "t768_h16_dh128": (2, 16, 768, 128, jnp.bfloat16, (2, 1, 1, 768), True,
                       0.1, None, (4, 256, 256)),
    "t768_h12_dh64": (2, 12, 768, 64, jnp.bfloat16, (2, 12, 768, 768), False,
                      0.1, None, (6, 256, 256)),
    "t640_h16_dh128": (2, 16, 640, 128, jnp.bfloat16, None, True, 0.0, None,
                       (16, 128, 128)),
    # blocks of the whole of a t that is no power of two
    "t384": (2, 8, 384, 64, jnp.bfloat16, (2, 1, 384, 384), True, 0.1, None,
             (2, 384, 384)),
    # a caller's q block off the 128 lanes: dk/dv reads lse and delta as
    # columns
    "q_block_64": (2, 2, 256, 64, jnp.bfloat16, (2, 1, 1, 256), True, 0.0,
                   64, (2, 64, 256)),
}
_FUSED = {"olmoe", "olmoe_row_bias", "chip_smoke_bhtd"}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_bhtd_forward_and_backward_compile(case, one_chip, real_kernels):
    (b, h, t, dh, dtype, bias_shape, causal, p_drop, q_block,
     tile) = _CASES[case]
    assert fa.bhtd_tile(h, t, t, q_block, dh=dh) == tile
    form = fa.bhtd_bwd_form(
        h, t, t, q_block, dh=dh, itemsize=jnp.dtype(dtype).itemsize,
        p_drop=p_drop)
    assert form == ("fused" if case in _FUSED else "split")

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arg((b, h, t, dh), dtype)
    bias = None if bias_shape is None else arg(bias_shape, jnp.float32)
    seed = arg((), jnp.int32)

    def loss(q, k, v, bias, seed):
        out, lse = fa.flash_attention_with_lse(
            q, k, v, bias, seed if p_drop else None, None, p_drop,
            q_block, causal=causal)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, bias, seed).compile().as_text()
    _holds_the_calls(text, form)
    assert text.count("tpu_custom_call") >= len(_NAMES[form])


# (m, k, n, e) of a forward product [m, k] x [e, k, n] that gmm_tile
# admits on the chip: olmoe-train-s4096's two, and every way the tile
# can come out (each row tile; a width the VMEM cap narrows, where the
# raised vmem_limit_bytes has to hold; a width that is no power of two)
_GMM_CASES = {
    "olmoe_gate_up": (65536, 2048, 1024, 64),
    "olmoe_down": (65536, 1024, 2048, 64),
    "rows_512": (65536, 2048, 1024, 8),
    "rows_128": (8192, 2048, 1024, 64),
    "narrowed_4096": (65536, 4096, 4096, 8),
    "narrowed_8192": (65536, 8192, 2048, 8),
    "moonlight_1408": (65536, 2048, 1408, 64),
}


@pytest.mark.parametrize("case", sorted(_GMM_CASES))
def test_grouped_matmul_kernels_compile(case, one_chip, real_kernels):
    m, k, n, e = _GMM_CASES[case]
    bf = jnp.bfloat16
    tile = gm.gmm_tile(m, k, n, e, bf, "tpu", False)
    dx_tile = gm.gmm_tile(m, n, k, e, bf, "tpu", False)
    assert tile and dx_tile

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def three(lhs, rhs, g, sizes):
        return (gm.gmm(lhs, rhs, sizes, tile),
                gm.gmm(g, rhs, sizes, dx_tile, transpose_rhs=True,
                       name="moe.gmm.bwd_dx"),
                gm.tgmm(lhs, g, sizes, tile))

    text = jax.jit(three).lower(
        arg((m, k), bf), arg((e, k, n), bf), arg((m, n), bf),
        arg((e,), jnp.int32)).compile().as_text()
    for name in ("moe.gmm.fwd", "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"):
        assert name in text, name


# (m, k, n, e, live rows or None) of a weight-gradient call whose Adam
# step is taken inside the kernel: OLMoE's two matrices, a held share's
# (SDAR's 16 of 128 experts of 768, LFM2's 8 of 64 of 1536) and
# Nemotron's 1856 as the contraction (as the width it has no tile: tgmm
# and the update behind)
_ADAM_CASES = {
    "olmoe_gate_up": (65536, 2048, 1024, 64, None),
    "olmoe_down": (65536, 1024, 2048, 64, None),
    "sdar_gate_up": (65536, 2048, 768, 16, 8192),
    "sdar_down": (65536, 768, 2048, 16, 8192),
    "nemotron_down": (24576, 1856, 2688, 8, 1536),
    "lfm2moe_down": (32768, 1536, 2048, 8, 4096),
}


@pytest.mark.parametrize("decay", [False, True], ids=["adam", "adamw"])
@pytest.mark.parametrize("case", sorted(_ADAM_CASES))
def test_weight_gradient_kernel_with_adam_compiles(case, decay, one_chip,
                                                   real_kernels):
    """Mosaic takes ``moe.tgmm.bwd_dw_adam`` at ``adam_tile``'s tile
    inside the scoped VMEM the call asks for, and the compiled call
    writes the weight and both moments where it read them: no second
    buffer the size of a state tensor is allocated (the temporaries are
    the visits' bookkeeping and, for a contraction off the lanes, the
    rows' lane-padded copy that ``tgmm`` has too)."""
    m, k, n, e, live = _ADAM_CASES[case]
    bf, f32 = jnp.bfloat16, jnp.float32
    tile = gm.adam_tile(
        gm.gmm_tile(m, k, n, e, bf, "tpu", False, live_rows=live), k, n, e,
        live or m)
    assert tile and gm._adam_vmem_bytes(*tile, 2) <= gm._VMEM_CAP_BYTES

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(lhs, g, sizes, lr_t, lr_decay, *state):
        return gm.tgmm_adam(lhs, g, sizes, tile, gm.AdamStep(
            state, lr_t, lr_decay if decay else None, 0.9, 0.999, 1e-8))

    compiled = jax.jit(step, donate_argnums=(5, 6, 7)).lower(
        arg((m, k), bf), arg((m, n), bf), arg((e,), jnp.int32),
        arg((), f32), arg((), f32), *[arg((e, k, n), f32)] * 3).compile()
    assert "moe.tgmm.bwd_dw_adam" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 3 * 4 * e * k * n
    assert mem.temp_size_in_bytes < 4 * e * k * n


def test_a_width_off_the_lanes_keeps_the_two_passes():
    """Nemotron's up projection, 1856 wide: the kernel's own copies
    cannot slice a width off the lane tiling, so no fused tile."""
    tile = gm.gmm_tile(24576, 2688, 1856, 8, jnp.bfloat16, "tpu", False,
                       live_rows=1536)
    assert tile == (128, 2688, 640)
    assert gm.adam_tile(tile, 2688, 1856, 8, 1536) is None


def test_grouped_query_attention_compiles(one_chip, real_kernels):
    """Qwen3-Next's attention layer: 16 query heads over 2 key/value
    heads of 256 at 8192 positions, one head a step at blocks of 512;
    the backward call's grid walks a group's 8 heads and writes [b, 2,
    t, 256] from rows resident in VMEM."""
    b, h, hk, t, dh = 1, 16, 2, 8192, 256
    assert fa.bhtd_tile(h, t, t, dh=dh, group=h // hk) == (1, 512, 512)

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(hk), arg(hk)).compile()
    text = compiled.as_text()
    _holds_the_calls(text, "fused")
    # K and V are read where they lie: nothing [b, 16, t, 256] besides
    # q, out and their gradients
    assert "bf16[1,2,8192,256]" in text


def test_latent_attention_compiles(one_chip, real_kernels):
    """JoyAI-LLM-Flash's attention call: 32 heads whose queries and keys
    are 192 wide (a lane and a half) over values of 128 at 4096
    positions, one head a step at blocks of 512; q and k go in as they
    are (nothing [.., 256]), out and dv are [b, 32, t, 128]."""
    b, h, t, dk, dv = 1, 32, 4096, 192, 128
    assert fa.bhtd_tile(h, t, t, dh=dk, dv=dv) == (1, 512, 512)

    def arg(width):
        return jax.ShapeDtypeStruct((b, h, t, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(dk), arg(dk), arg(dv)).compile()
    text = compiled.as_text()
    _holds_the_calls(text, "fused")
    assert "bf16[1,32,4096,192]" in text and "4096,256]" not in text
    # q, k and v in: 2 x (2 x 192 + 128) bytes a position and head, no
    # padded copy among them
    assert compiled.memory_analysis().argument_size_in_bytes \
        == b * h * t * (2 * dk + dv) * 2


def test_latent_attention_in_two_parts_compiles(one_chip, real_kernels):
    """The latent cells' call since PR 70: q and k of 128 features, QPe
    [b, 32, t, 64] and KPe's ONE head [b, 1, t, 64] as operands of the
    kernels' own, at the wide call's tile and form; nothing 192 or 256
    wide exists, no copy of the shared head ([b, 32, t, 64] keys), and
    the backward's results are dq, dk, dv, dq_pe and a dk_pe a QUERY
    head, which XLA sums into KPe's one head."""
    b, h, t, dh, r, dv = 1, 32, 4096, 128, 64, 128
    assert fa.bhtd_parts(h, t, t, dh=dh, r=r, hp=1, dv=dv)

    def arg(heads, width):
        return jax.ShapeDtypeStruct((b, heads, t, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v, q_pe, k_pe):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                               q_pe=q_pe, k_pe=k_pe)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg(h, dh), arg(h, dh), arg(h, dv), arg(h, r), arg(1, r)).compile()
    text = compiled.as_text()
    _holds_the_calls(text, "fused")
    assert "4096,192]" not in text and "4096,256]" not in text
    bwd = next(line for line in text.splitlines()
               if "custom-call(" in line and "attn.bhtd.bwd" in line)
    results = bwd.split(" custom-call(")[0]
    assert results.count("bf16[1,32,4096,128]") == 3
    assert results.count("bf16[1,32,4096,64]") == 2
    assert "bf16[1,1,4096,64]" in text      # KPe and its summed gradient
    # q, k, v, QPe and KPe in, no padded or copied one among them
    assert compiled.memory_analysis().argument_size_in_bytes \
        == b * t * (h * (2 * dh + dv + r) + r) * 2


def test_held_share_grouped_matmuls_compile(one_chip, real_kernels):
    """One chip's 32 of 512 experts: a buffer of 81,920 rows of which an
    even router fills 5,120, so the row tile is 128."""
    m, k, n, e, live = 81920, 2048, 512, 32, 5120
    bf = jnp.bfloat16
    tile = gm.gmm_tile(m, k, n, e, bf, "tpu", False, live_rows=live)
    dx_tile = gm.gmm_tile(m, n, k, e, bf, "tpu", False, live_rows=live)
    assert tile == (128, 2048, 512) and dx_tile == (128, 512, 2048)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def three(lhs, rhs, g, sizes):
        return (gm.gmm(lhs, rhs, sizes, tile),
                gm.gmm(g, rhs, sizes, dx_tile, transpose_rhs=True,
                       name="moe.gmm.bwd_dx"),
                gm.tgmm(lhs, g, sizes, tile))

    text = jax.jit(three).lower(
        arg((m, k), bf), arg((e, k, n), bf), arg((m, n), bf),
        arg((e,), jnp.int32)).compile().as_text()
    for name in ("moe.gmm.fwd", "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"):
        assert name in text, name


def test_windowed_attention_compiles(one_chip, real_kernels):
    """SmallThinker's window layers: 28 query heads over 4 key/value
    heads of 128 (a group of 7) at 16,384 positions that see the last
    4096, one head a step at blocks of 512. Both grids' inner axis is
    the band's 9 blocks, not the sequence's 32, and the two branches of
    a windowed step (masked on an edge block, plain inside the band)
    fit Mosaic's VMEM beside the backward call's resident rows."""
    b, h, hk, t, dh, window = 1, 28, 4, 16384, 128, 4096
    assert fa.bhtd_tile(h, t, t, dh=dh, group=h // hk) == (1, 512, 512)
    assert fa._k_steps(window, 32, 32, 512, 512) == 9
    assert fa._q_steps(window, 32, 32, 512, 512) == 9

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                               window=window)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(hk), arg(hk)).compile().as_text()
    _holds_the_calls(text, "fused")
    assert "bf16[1,4,16384,128]" in text


# (b, query heads, key/value heads, t, dh, dv, window) of the decoder
# cells' attention calls
_CELL_CALLS = {
    "laguna_w512": (1, 64, 8, 8192, 128, 128, 512),
    "laguna_global": (1, 48, 8, 8192, 128, 128, None),
    "smallthinker_w4096": (1, 28, 4, 16384, 128, 128, 4096),
    "smallthinker_global": (1, 28, 4, 16384, 128, 128, None),
    "joyai": (1, 32, 32, 4096, 192, 128, None),
    "qwen3next": (1, 16, 2, 8192, 256, 256, None),
    "olmoe": (2, 16, 16, 4096, 128, 128, None),
    "lfm2moe": (1, 32, 8, 8192, 64, 64, None),
    # (one softmax map of a differential layer's 40 / 20 heads of 64:
    # 20 / 10 pair-heads over values of 128)
    "phi4flash_w512": (1, 20, 10, 4096, 64, 128, 512),
    "phi4flash_global": (1, 20, 10, 4096, 64, 128, None),
    "nemotron3nano": (1, 32, 2, 4096, 128, 128, None),
}


def _lowered_bwd(call, one_chip):
    b, h, hk, t, dh, dv, window = _CELL_CALLS[call]

    def arg(heads, width, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct((b, heads, t, width), dt,
                                    sharding=one_chip)

    def bwd(q, k, v, out, lse, g):
        return fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                      causal=True, window=window)

    return jax.jit(bwd).lower(
        arg(h, dh), arg(hk, dh), arg(hk, dv), arg(h, dv),
        arg(h, 1, jnp.float32), arg(h, dv))


@pytest.mark.parametrize("call", sorted(_CELL_CALLS))
def test_fused_backward_compiles_at_the_cells_calls(call, one_chip,
                                                    real_kernels):
    """The backward of every BHTD call of the eight decoder cells is ONE
    Mosaic call whose resident rows (dq for a query head; dk and dv for
    a group's key/value head) fit the VMEM it asks for, and nothing
    gradient-sized leaves it besides dq, dk and dv."""
    b, h, hk, t, dh, dv, _ = _CELL_CALLS[call]
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=h // hk, dv=dv) == "fused"
    assert fa._bwd_vmem_bytes(t, t, dh, dv, h // hk, 512, 512, 2) \
        <= fa._BWD_VMEM_CAP_BYTES
    compiled = _lowered_bwd(call, one_chip).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _calls(text) == {"attn.bhtd.bwd"}
    # (the result tuple's table besides)
    assert 0 <= compiled.memory_analysis().output_size_in_bytes \
        - 2 * b * t * (h * dh + hk * (dh + dv)) < 4096


def _compiled_step(call, one_chip, q_block=None):
    """A call's forward and backward jitted as ONE function, as a cell's
    step holds them: the compiled text."""
    b, h, hk, t, dh, dv, window = _CELL_CALLS[call]

    def arg(heads, width):
        return jax.ShapeDtypeStruct((b, heads, t, width), jnp.bfloat16,
                                    sharding=one_chip)

    def step(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, q_block=q_block,
                                          causal=True, window=window)
        return out, fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, q_block=q_block, causal=True,
            window=window)

    return jax.jit(step).lower(
        arg(h, dh), arg(hk, dh), arg(hk, dv), arg(h, dv)).compile().as_text()


def _fwd_results(text):
    """The result shapes of the ``attn.bhtd.fwd`` custom call."""
    (line,) = [l for l in text.splitlines()
               if "custom-call(" in l and "/attn.bhtd.fwd/" in l]
    return re.findall(r"\w+\[[\d,]*\]", line.split(" custom-call(")[0])


@pytest.mark.parametrize("call", sorted(_CELL_CALLS))
def test_the_logsumexp_crosses_the_step_as_rows(call, one_chip,
                                                real_kernels):
    """Every BHTD call of the eight decoder cells, forward and backward
    in one jit: ``attn.bhtd.fwd`` writes its logsumexp as [b, h, 1, t]
    rows, ``attn.bhtd.bwd`` reads that buffer, and no float32
    [b, h, t, 1] (a lane tile of 512 bytes a row on the chip) exists,
    neither lse nor delta; nothing runs between the two but delta's
    sum."""
    b, h, hk, t, dh, dv, _ = _CELL_CALLS[call]
    tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk, dv=dv)
    assert fa.bhtd_stats_form(tile, t) == "rows"
    text = _compiled_step(call, one_chip)
    assert _fwd_results(text) == [f"bf16[{b},{h},{t},{dv}]",
                                  f"f32[{b},{h},1,{t}]"]
    assert f"f32[{b},{h},{t},1]" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _calls(text) == {"attn.bhtd.fwd", "attn.bhtd.bwd"}


def test_a_q_block_off_the_lanes_keeps_the_column(one_chip, real_kernels):
    """A caller's q block of 64 cannot be cut from a row: the forward
    writes the [b, h, t, 1] column and the pair reads it, as before."""
    b, h, hk, t, dh, dv, _ = _CELL_CALLS["olmoe"]
    tile = fa.bhtd_tile(h, t, t, 64, dh=dh)
    assert tile[1] == 64 and fa.bhtd_stats_form(tile, t) == "column"
    assert fa.bhtd_bwd_form(h, t, t, 64, dh=dh) == "split"
    text = _compiled_step("olmoe", one_chip, q_block=64)
    assert _fwd_results(text) == [f"bf16[{b},{h},{t},{dv}]",
                                  f"f32[{b},{h},{t},1]"]
    _holds_the_calls(text, "split")


@pytest.mark.parametrize("call", ["laguna_w512", "smallthinker_w4096",
                                  "joyai", "qwen3next"])
def test_edge_sub_tiles_compile_alone_and_inside_a_while_body(
        call, one_chip, real_kernels):
    """The calls whose backward walks its edge blocks in sub-tiles (a
    band of one block where every block is an edge, a band of eight, a
    head of 192 over 128, a head of 256 under a group of 8), forward
    and the ONE backward call: alone, and as a ``run_steps`` window
    lowers them, inside a While body, under the VMEM limits the calls
    set themselves (the backward's ``_bwd_vmem_limit``, Mosaic's default
    forward)."""
    b, h, hk, t, dh, dv, window = _CELL_CALLS[call]
    tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk, dv=dv)
    assert tile == (1, 512, 512)
    sub = fa.bhtd_edge_tile(tile, True)
    assert sub == fa._edge_tile(512, 512) and sub is not None
    assert fa._bwd_vmem_limit(t, t, dh, dv, h // hk, 512, 512, 2) \
        <= fa._BWD_VMEM_CAP_BYTES * 5 // 4

    def arg(heads, width):
        return jax.ShapeDtypeStruct((b, heads, t, width), jnp.bfloat16,
                                    sharding=one_chip)

    def step(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=True,
                                          window=window)
        dq, dk, dv_ = fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, window=window)
        return dq, dk, dv_, out

    def steps(q, k, v, g):
        return jax.lax.fori_loop(0, 3, lambda _, x: step(*x), (q, k, v, g))

    args = (arg(h, dh), arg(hk, dh), arg(hk, dv), arg(h, dv))
    for f, loop in ((step, False), (steps, True)):
        text = jax.jit(f).lower(*args).compile().as_text()
        assert (" while(" in text) == loop
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert _calls(text) == {"attn.bhtd.fwd", "attn.bhtd.bwd"}


def test_the_split_pair_compiles_where_the_rows_pass_the_cap(
        one_chip, real_kernels, monkeypatch):
    """``bhtd_bwd_form`` alone chooses: with no room for a resident row
    the same call lowers as the pair."""
    monkeypatch.setattr(fa, "_BWD_VMEM_CAP_BYTES", 2**20)
    b, h, hk, t, dh, dv, _ = _CELL_CALLS["smallthinker_w4096"]
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=h // hk, dv=dv) == "split"
    text = _lowered_bwd("smallthinker_w4096", one_chip).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _calls(text) == {"attn.bhtd.bwd_dq", "attn.bhtd.bwd_dkv"}


def test_smallthinker_held_grouped_matmuls_compile(one_chip, real_kernels):
    """One chip's 8 of 64 ReGLU experts of 768 over a hidden size of
    2560 (5 x 512: the first contraction that is no power of two): a
    buffer of 98,304 rows of which an even router fills 12,288."""
    m, k, n, e, live = 98304, 2560, 768, 8, 12288
    bf = jnp.bfloat16
    tile = gm.gmm_tile(m, k, n, e, bf, "tpu", False, live_rows=live)
    dx_tile = gm.gmm_tile(m, n, k, e, bf, "tpu", False, live_rows=live)
    assert tile is not None and dx_tile is not None
    assert k % tile[1] == 0 and n % tile[2] == 0

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def three(lhs, rhs, g, sizes):
        return (gm.gmm(lhs, rhs, sizes, tile),
                gm.gmm(g, rhs, sizes, dx_tile, transpose_rhs=True,
                       name="moe.gmm.bwd_dx"),
                gm.tgmm(lhs, g, sizes, tile))

    text = jax.jit(three).lower(
        arg((m, k), bf), arg((e, k, n), bf), arg((m, n), bf),
        arg((e,), jnp.int32)).compile().as_text()
    for name in ("moe.gmm.fwd", "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"):
        assert name in text, name


# (t, hk, hv): the cell's call; a sequence of fewer chunks than a grid
# step holds, hk = hv (the block is the whole padded sequence)
@pytest.mark.parametrize("t,hk,hv", [(8192, 16, 32), (200, 2, 2)],
                         ids=["qwen3next_s8192", "t200_one_step"])
def test_gated_delta_rule_kernels_compile(t, hk, hv, one_chip, real_kernels):
    """Qwen3-Next's DeltaNet layer as qwen3next-train-s8192 lowers it:
    16 key and 32 value heads of 128, a key head's two value heads and
    8 chunks of 64 a grid step, forward and the backward pass from the
    saved states: the substitution's lane rolls, the merges' batched
    float32 products against a block diagonal, the transposed
    float32 products and the blocks' VMEM pass Mosaic."""
    bf = jnp.bfloat16
    tile = gdr.gdn_tile(t, hk, hv, 128, 128, 64, bf, "tpu", False)
    assert tile == (hv // hk, min(8, -(-t // 64)))

    def arg(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def both(q, k, v, g, beta, do):
        o, states = gdr.gated_delta_rule_fwd(q, k, v, g, beta, tile)
        return o, gdr.gated_delta_rule_bwd(q, k, v, g, beta, states, do,
                                           tile)

    qk, v = arg((1, t, hk, 128)), arg((1, t, hv, 128))
    gate = arg((1, t, hv), jnp.float32)
    text = jax.jit(both).lower(qk, qk, v, gate, gate, v).compile().as_text()
    for name in ("gdn.rule.fwd", "gdn.rule.bwd"):
        assert name in text, name
    # no copy in front: q, k, v are read where they lie, nothing float32
    # is staged heads-first or repeated to the value heads (o alone
    # leaves heads-first, in bf16)
    assert f"f32[1,{hv},{t},128]" not in text


def _gdn_cell_call(one_chip):
    """qwen3next-train-s8192's call of the delta rule as shapes on the
    described chip: (q, k, v, g, beta, dO, States), its tile."""
    bf, t, hk, hv = jnp.bfloat16, 8192, 16, 32

    def arg(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    qk, v = arg((1, t, hk, 128)), arg((1, t, hv, 128))
    gate = arg((1, t, hv), jnp.float32)
    states = arg((t // 64, 1, hv, 128, 128))
    tile = gdr.gdn_tile(t, hk, hv, 128, 128, 64, bf, "tpu", False)
    assert tile == (2, 8)
    return (qk, qk, v, gate, gate, v, states), tile


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_gated_delta_rule_passes_are_one_mosaic_call_each(which, one_chip,
                                                          real_kernels):
    """At the cell's call each pass is ONE Mosaic call: the pre-pass
    (what a grid step makes once of its chunks), the state loop and the
    pass behind it are parts of one kernel with their hand-offs in VMEM
    scratch, not calls with tensors in HBM between them."""
    (q, k, v, g, beta, do, states), tile = _gdn_cell_call(one_chip)
    if which == "fwd":
        lowered = jax.jit(lambda *x: gdr.gated_delta_rule_fwd(
            *x, tile)).lower(q, k, v, g, beta)
    else:
        lowered = jax.jit(lambda *x: gdr.gated_delta_rule_bwd(
            *x, tile)).lower(q, k, v, g, beta, states, do)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"gdn.rule.{which}" in text
    assert " while(" not in text


def test_gated_delta_rule_passes_compile_inside_a_while_body(one_chip,
                                                             real_kernels):
    """As a ``run_steps`` window lowers them: both passes inside a While
    body, still one Mosaic call each, with the scratch of the pre-pass
    under the VMEM limit the calls ask for there too."""
    (q, k, v, g, beta, do, _), tile = _gdn_cell_call(one_chip)

    def window(q, k, v, g, beta, do):
        def step(_, qkv):
            q, k, v = qkv
            o, states = gdr.gated_delta_rule_fwd(q, k, v, g, beta, tile)
            dq, dk, dv, _, _ = gdr.gated_delta_rule_bwd(
                q, k, v, g, beta, states, do + o, tile)
            return dq, dk, dv
        return jax.lax.fori_loop(0, 3, step, (q, k, v))

    text = jax.jit(window).lower(q, k, v, g, beta, do).compile().as_text()
    assert " while(" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("gdn.rule.fwd", "gdn.rule.bwd"):
        assert name in text, name


def test_causal_conv_kernels_compile(one_chip, real_kernels):
    """The conv in front of Qwen3-Next's delta rule as
    qwen3next-train-s8192 lowers it: [1, 8192, 8192] bf16, 4 taps, silu,
    blocks of 1024 x 512, forward and the backward pass from X alone:
    the sublane rolls over [halo; rows], the 16-row block in front and
    the dW block that stays over a lane block's walk pass Mosaic."""
    bf, t, c, taps = jnp.bfloat16, 8192, 8192, 4
    tile = cc.conv_tile(t, c, taps, bf, "tpu", False)
    assert tile == (1024, 512)
    x = jax.ShapeDtypeStruct((1, t, c), bf, sharding=one_chip)
    w = jax.ShapeDtypeStruct((c, taps), jnp.float32, sharding=one_chip)

    def both(x, w, dy):
        return (cc.causal_conv_fwd(x, w, tile),
                cc.causal_conv_bwd(x, w, dy, tile))

    text = jax.jit(both).lower(x, w, x).compile().as_text()
    for name in ("gdn.conv.fwd", "gdn.conv.bwd"):
        assert name in text, name
    # nothing float32 of X's size: no padded copy, no float32 Y
    assert f"f32[1,{t}," not in text and f"f32[1,{t + taps - 1}," not in text


def test_gated_short_conv_kernels_compile(one_chip, real_kernels):
    """LFM2's mixer as lfm2moe-train-s8192 lowers it: the fused
    projection [1, 8192, 6144] bf16 = [B | C | u], 3 taps, blocks of
    1024 x 256: three BlockSpecs on the ONE operand at lane-block
    offsets of 8 and 16 blocks, the backward's grid axis over the three
    ranges of its one output and its two waiting ranges in VMEM pass
    Mosaic; no slice of the projection, nothing float32 of its size and
    no concatenation reaches HBM."""
    bf, t, c, taps = jnp.bfloat16, 8192, 2048, 3
    tile = cc.conv_tile(t, c, taps, bf, "tpu", False, gated=True)
    assert tile == (1024, 256)
    x = jax.ShapeDtypeStruct((1, t, 3 * c), bf, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((1, t, c), bf, sharding=one_chip)
    w = jax.ShapeDtypeStruct((c, taps), jnp.float32, sharding=one_chip)

    def both(x, w, dy):
        return (cc.gated_conv_fwd(x, w, tile),
                cc.gated_conv_bwd(x, w, dy, tile))

    text = jax.jit(both).lower(x, w, dy).compile().as_text()
    for name in ("sconv.gated.fwd", "sconv.gated.bwd"):
        assert name in text, name
    assert f"f32[1,{t}," not in text and "concatenate" not in text


# n tokens, k a token, d, held experts: the four cells with expert layers
_PAIR_SUMS = {"smallthinker": (16384, 6, 2560, 8),
              "qwen3next": (8192, 10, 2048, 32),
              "joyai": (4096, 8, 2048, 16), "olmoe": (8192, 8, 2048, 64)}


@pytest.mark.parametrize("cell", sorted(_PAIR_SUMS))
def test_pair_sum_kernel_compiles_at_the_cells_calls(cell, one_chip,
                                                     real_kernels):
    """The token-major sums' kernel (PR 41) as the four MoE cells lower
    it, with a weight (``pairs.sum.combine``) and without
    (``pairs.sum.dispatch_grad``): the row DMAs in whole groups of 16,
    the dynamic walk of the segments, the two staging buffers and the
    stacked three-piece left operand pass Mosaic, and no gathered
    [k, n, d] copy or float32 [n, d] target is left in the program."""
    n, k, d, held = _PAIR_SUMS[cell]
    tile = ps.sum_tile(n, k, d, jnp.bfloat16, "tpu", False)
    assert tile == (128, 32)

    def at(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(rows, slot, sizes, top_w):
        return (ps.pair_sum(rows, slot, sizes, tile, top_w,
                            name="pairs.sum.combine"),
                ps.pair_sum(rows, slot, sizes, tile,
                            name="pairs.sum.dispatch_grad"))

    text = jax.jit(both).lower(
        at((n * k, d), jnp.bfloat16), at((n, k), jnp.int32),
        at((held,), jnp.int32), at((n, k), jnp.float32)).compile().as_text()
    for name in ("pairs.sum.combine", "pairs.sum.dispatch_grad"):
        assert name in text, name
    assert f"[{k},{n},{d}]" not in text and f"f32[{n},{d}]" not in text


# (b, t, q heads, k heads, dh[, rotary_dim, yarn scaling, periods, with
# the heads' gains]) of the cells whose rotary embedding rope_tile
# takes, and a head two vregs wide. sdar's call norms each head in the
# same pass over a row of two runs of the positions.
# Laguna's window layers turn the whole head of 64 + 8 heads plainly, its
# full layers 64 of a head's 128 features of 48 + 8 heads under yarn
_ROPES = {"smallthinker": (1, 16384, 28, 4, 128),
          "olmoe": (2, 4096, 16, 16, 128), "dh256": (1, 8192, 16, 2, 256),
          "laguna_window": (1, 8192, 64, 8, 128),
          "laguna_full": (1, 8192, 48, 8, 128, 64, rope.Yarn(
              64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)),
          "sdar_gains": (1, 8192, 32, 4, 128, None, None, 2, True)}


@pytest.mark.parametrize("tokens", [True, False],
                         ids=["token_major", "head_major"])
@pytest.mark.parametrize("cell", sorted(_ROPES))
def test_rope_kernels_compile_at_the_cells_calls(cell, tokens, one_chip,
                                                 real_kernels):
    """``rope.fwd`` / ``rope.bwd`` (PR 42) as the two cells lower them,
    q and k token-major in and head-major out (and back), and head-major
    both ways: the lane-blocks of a head in a [rows, h dh] block, the
    lane roll by half a head (whole vregs at dh 256) and k's blocks
    riding in the first head step pass Mosaic, and no float32 copy of q
    is left in the program. A part of a head one vreg wide (PR 57): the
    two lane rolls and the select of its partner lanes pass too. With
    the heads' gains (PR 66): the lane sums, the rsqrt of a [rows, 1]
    column, the backward's third operand (counted by ``rope_tile``) and
    the (8, dh) tiles of the gains' partial sums pass as well."""
    call = _ROPES[cell]
    b, t, h, hk, dh, part, scaling, periods, norm = (
        *call, *(None, None, 1, False)[len(call) - 5:])
    tile = rope.rope_tile(b, t, h, dh, part, False, jnp.bfloat16, hk=hk,
                          backend="tpu", on_mesh=False, periods=periods,
                          norm=norm)
    assert tile == (256, h)

    def at(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(q, k, dq, dk, *gains):
        kw = dict(tokens=tokens, scaling=scaling, rotary_dim=part,
                  periods=periods)
        if norm:
            kw.update(gains=gains, eps=1e-6)
        return (rope.rope_fwd(q, k, 1e6, tile, **kw),
                rope.rope_bwd(dq, dk, 1e6, tile, **kw,
                              **({"x": (q, k)} if norm else {})))

    heads = (at(b, h, t, dh), at(b, hk, t, dh))
    ins = (at(b, t, h, dh), at(b, t, hk, dh)) if tokens else heads
    text = jax.jit(both).lower(
        *ins, *heads, *[at(dh, dtype=jnp.float32)] * (2 * norm)
    ).compile().as_text()
    for name in ("rope.fwd", "rope.bwd"):
        assert name in text, name
    assert f"f32[{b},{h},{t}," not in text and f"f32[{b},{t},{h}" not in text


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "memory"])
def test_selective_scan_kernels_compile(gated, one_chip, real_kernels):
    """The selective scan as phi4flash-train-s4096 lowers it: [1, 4096,
    5120] bf16 with 16 states, blocks of 128 positions x 1024 channels,
    gated (layer 14) and not (layer 16, the memory source): the bf16
    [rows, 8, 128] blocks, B and C in SMEM, the sublane and lane
    butterflies and the 8 MB scratch of recomputed states pass Mosaic,
    and nothing of size t x e x n exists."""
    bf, f32, t, e, n = jnp.bfloat16, jnp.float32, 4096, 5120, ss.STATE
    tile = ss.ssm_tile(t, e, n, bf, "tpu", False)
    assert tile == (128, 1024)
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    x, bc = S((1, t, e), bf), S((1, t, n), bf)
    a, row = S((e, n), f32), S((e,), f32)

    def both(x, dt, a, b, c, d, z, bias, dy):
        z = z if gated else None
        y, states = ss.selective_scan_fwd(x, dt, a, b, c, d, z, bias, tile)
        return y, ss.selective_scan_bwd(x, dt, a, b, c, d, z, bias, states,
                                        dy, tile)

    text = jax.jit(both).lower(x, x, a, bc, bc, row, x, row,
                               x).compile().as_text()
    for name in ("ssm.scan.fwd", "ssm.scan.bwd"):
        assert name in text, name
    assert f"[1,{t},{e},{n}]" not in text and f"[{t},{e},{n}]" not in text
    # the saved states: one a block of 128 positions, float32
    assert f"f32[1,{t // 128},{n},{e // 128},128]" in text


def test_causal_conv_kernels_with_a_bias_compile(one_chip, real_kernels):
    """Mamba's convolution as phi4flash-train-s4096 lowers it: [1, 4096,
    5120] bf16, 4 taps, a bias in front of the silu (a row behind the
    taps of the W operand), forward and backward."""
    bf, t, c, taps = jnp.bfloat16, 4096, 5120, 4
    tile = cc.conv_tile(t, c, taps, bf, "tpu", False)
    assert tile == (1024, 512)
    x = jax.ShapeDtypeStruct((1, t, c), bf, sharding=one_chip)
    w = jax.ShapeDtypeStruct((c, taps), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)

    def both(x, w, b, dy):
        return (cc.causal_conv_fwd(x, w, tile, bias=b),
                cc.causal_conv_bwd(x, w, dy, tile, bias=b))

    text = jax.jit(both).lower(x, w, b, x).compile().as_text()
    for name in ("gdn.conv.fwd", "gdn.conv.bwd"):
        assert name in text, name
    assert f"f32[1,{t}," not in text


@pytest.mark.parametrize("window", [None, 512], ids=["full", "w512"])
def test_differential_attention_maps_compile(window, one_chip, real_kernels):
    """One softmax map of phi4flash-train-s4096's attention layers: 20
    query over 10 key pair-heads of 64 over values of 128 x 4096, with
    and without the 512-wide window (blocks of 512: a band of two
    blocks a row), forward and the ONE backward call."""
    bf, t, h, hk, dh, dv = jnp.bfloat16, 4096, 20, 10, 64, 128
    assert fa.bhtd_tile(h, t, t, dh=dh, group=2, dv=dv) == (1, 512, 512)
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=2, dv=dv) == "fused"
    S = lambda heads, width: jax.ShapeDtypeStruct(
        (1, heads, t, width), bf, sharding=one_chip)

    def both(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, causal=True,
                                          window=window, scale=dh ** -0.5)
        return out, fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, window=window,
            scale=dh ** -0.5)

    text = jax.jit(both).lower(S(h, dh), S(hk, dh), S(hk, dv),
                               S(h, dv)).compile().as_text()
    for name in ("attn.bhtd.fwd", "attn.bhtd.bwd"):
        assert name in text, name
    assert "attn.bhtd.bwd_dq" not in text


# rows a step, rows of the table, width: the two cells whose table is
# 2560 wide, and the widest table
_EMBED_GRADS = {"phi4flash": (4096, 25008, 2560),
                "smallthinker": (16384, 18992, 2560),
                "olmoe": (8192, 50304, 2048)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("cell", sorted(_EMBED_GRADS))
def test_embed_grad_kernel_compiles_at_the_cells_calls(cell, dtype, one_chip,
                                                       real_kernels):
    """``embed.grad`` (PR 46) as the cells lower it: the prefetched list
    of (tile, group) steps in the index maps, a table that ends inside a
    tile, the float32 rows' three bf16 pieces pass Mosaic, and XLA's
    row-by-row scatter is not in the program."""
    n, vocab, d = _EMBED_GRADS[cell]
    tile = eg.embed_grad_tile(n, vocab, d, dtype, "tpu", False)
    assert tile == (128, 128)
    g = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda g, ids: eg.embed_grad(g, ids, vocab, tile)).lower(
        g, ids).compile().as_text()
    assert "embed.grad" in text and " scatter(" not in text


# batch, positions, width, vocabulary, soft labels: the cell whose head
# is its largest scope, the longest call (a vocabulary off the 128
# lanes), and the label-smoothed one
_LOSS_HEADS = {"olmoe": (2, 4096, 2048, 50304, False),
               "smallthinker": (1, 16384, 2560, 18992, False),
               "tbase": (128, 256, 512, 10000, True)}


@pytest.mark.parametrize("call", sorted(_LOSS_HEADS))
def test_loss_head_step_reads_its_logits_and_copies_none(call, one_chip,
                                                         real_kernels):
    """A Program that is only a head (chip_smoke.loss_head_program), its
    train step compiled for the v5e: no fusion of it writes a float32
    tensor of the logits' size (on hard labels: smoothed labels ARE
    one), none gathers from one, none holds a second exponential over
    one; and on hard labels the step's temporaries would not hold that
    tensor (PR 52: softmax_with_cross_entropy and its own grad op)."""
    import chip_smoke
    from benchmarks import xent_candidates

    batch, seq, width, vocab, soft = _LOSS_HEADS[call]
    main, _, loss, _ = chip_smoke.loss_head_program(batch, seq, width, vocab,
                                                    soft=soft)
    compiled = chip_smoke.lower_train_step(main, loss, seq, batch,
                                          one_chip).compile()
    rows = xent_candidates.fusions(compiled.as_text(), batch * seq, vocab)
    assert len(rows) >= 4, rows     # the pass, the three matmuls
    for row in rows:
        assert row["exp"] <= 1 and not row["gather"], row
        assert soft or not row["writes_f32_logits"], row
    if not soft:
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 1.03 * 4 * batch * seq * vocab)


# The BTHD-small pair at the calls of the three cells that lower it (PR
# 49): (b, t, heads of 64, rows of the bias [b, 1, rows, t]); dropout 0.1
# as the cells have it. transformer-base's encoder and cross attention
# carry the pad bias, its decoder's self attention the causal one.
_SMALL_CALLS = {
    "tbase_pad": (128, 256, 8, 1),
    "tbase_causal": (128, 256, 8, 256),
    "dp4_pad": (32, 256, 8, 1),
    "bert": (256, 128, 12, 1),
}


@pytest.mark.parametrize("call", sorted(_SMALL_CALLS))
def test_bthd_small_pair_compiles_at_the_cells_calls(call, one_chip,
                                                     real_kernels):
    """Forward and backward are ONE Mosaic call each; the backward makes
    delta itself, so XLA reduces nothing beside it."""
    b, t, h, bias_rows = _SMALL_CALLS[call]
    assert fa.bthd_family(t, t, h, 64) == "bthd_small"

    def arg(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, bias = arg(b, t, h, 64), arg(b, 1, bias_rows, t, dt=jnp.float32)
    seed, lse = arg(dt=jnp.int32), arg(b, t, h, 1, dt=jnp.float32)
    fwd = jax.jit(lambda q, k, v, bias, seed: fa.flash_attention_bthd_fwd(
        q, k, v, bias, seed, None, 0.1)).lower(x, x, x, bias, seed)
    bwd = jax.jit(
        lambda q, k, v, bias, seed, out, lse, g: fa.flash_attention_bthd_bwd(
            q, k, v, bias, seed, out, lse, g, None, 0.1)
    ).lower(x, x, x, bias, seed, x, lse, x)
    for lowered, name in ((fwd, "fwd"), (bwd, "bwd")):
        text = lowered.compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert f"attn.bthd_small.{name}/pallas_call" in text
        assert " reduce(" not in text


def test_bthd_small_pair_compiles_inside_a_while_body(one_chip, real_kernels):
    """As a ``run_steps`` window lowers it: the backward at its chunk of
    256 rows asks for no VMEM beyond Mosaic's default (a higher limit
    measured slower), and a While body is where the K-blocked backward
    once landed over it."""
    b, t, h, _ = _SMALL_CALLS["tbase_causal"]

    def arg(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, bias = arg(b, t, h, 64), arg(b, 1, t, t, dt=jnp.float32)
    seed, lse = arg(dt=jnp.int32), arg(b, t, h, 1, dt=jnp.float32)

    def window(q, k, v, bias, seed, out, lse, g):
        def step(_, qkv):
            dq, dk, dv = fa.flash_attention_bthd_bwd(
                *qkv, bias, seed, out, lse, g, None, 0.1)
            o, _ = fa.flash_attention_bthd_fwd(dq, dk, dv, bias, seed, None,
                                               0.1)
            return o, dk, dv
        return jax.lax.fori_loop(0, 3, step, (q, k, v))

    text = jax.jit(window).lower(
        x, x, x, bias, seed, x, lse, x).compile().as_text()
    assert " while(" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2


# sdar-train-s4096's attention call: a row of 8192 positions (the noised
# copy and the clean copy of 4096 data tokens) at 32 / 4 heads of 128
# under block diffusion's mask, blocks of 4 (PR 61)
_BD_CALL = (1, 32, 4, 8192, 128, 4)


def test_the_block_masked_call_compiles_in_the_two_kernels(one_chip,
                                                           real_kernels):
    """Forward and backward in one jit: ``attn.bhtd.fwd`` and the ONE
    ``attn.bhtd.bwd``, the logsumexp handed over as rows, and no tensor
    of [t, t] scores anywhere in the compiled step (32 heads of them
    would be 8.6 GB in float32)."""
    b, h, hk, t, dh, block = _BD_CALL
    kw = dict(dh=dh, group=h // hk, block_diffusion=block)
    assert fa.bhtd_tile(h, t, t, **kw) == (1, 512, 512)
    assert fa.bhtd_bwd_form(h, t, t, **kw) == "fused"
    assert fa.bhtd_family(h, t, t, **kw) == "bhtd"
    # 80 blocks of 512 x 512 a head either way for L^2 + B L live pairs
    for form in (None, "fused"):
        assert fa.bhtd_pairs(t, t, (1, 512, 512), False, form=form,
                             block_diffusion=block) \
            == (80 * 512 * 512, 4096 * 4096 + 4 * 4096)

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, t, dh), jnp.bfloat16,
                                    sharding=one_chip)

    def step(q, k, v, g):
        out, lse = fa.flash_attention_fwd(q, k, v, block_diffusion=block)
        return out, fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                           block_diffusion=block)

    compiled = jax.jit(step).lower(arg(h), arg(hk), arg(hk), arg(h)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _calls(text) == {"attn.bhtd.fwd", "attn.bhtd.bwd"}
    assert _fwd_results(text) == [f"bf16[{b},{h},{t},{dh}]",
                                  f"f32[{b},{h},1,{t}]"]
    assert f"{t},{t}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


def test_the_block_masked_op_reports_band_skip(one_chip, real_kernels):
    """The cell's call through the op, forward and backward, lowered for
    the described v5e: one row each way on the BHTD kernels, ``band=skip``
    (the kernels walk the mask's live blocks), the backward one call."""
    import paddle_tpu as fluid
    from paddle_tpu import flags, layers, monitor
    from paddle_tpu.core import lowering
    from paddle_tpu.ops import attention_ops

    b, h, hk, t, dh, block = _BD_CALL
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = layers.data("q", shape=[h, t, dh], dtype="float32")
        k = layers.data("k", shape=[hk, t, dh], dtype="float32")
        q.stop_gradient = k.stop_gradient = False
        out = layers.scaled_dot_product_attention(q, k, k, dh ** -0.5,
                                                  block_diffusion=block)
        loss = layers.mean(out)
        fluid.backward.append_backward(loss)
    main._amp = True

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        low = lowering.lower_block(main, 0, ("q", "k"),
                                   (loss.name, "q@GRAD", "k@GRAD"))
        text = fluid.Executor._jit_for(low, None).lower(
            {}, {"q": aval((b, h, t, dh), "bfloat16"),
                 "k": aval((b, hk, t, dh), "bfloat16")},
            aval((2,), "uint32"), aval((), "uint32")).compile().as_text()
        rows = attention_ops.dispatch_counts(tiles=True, forms=True,
                                             stats=True, masks=True)
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = f"b{b} tq{t} tk{t} h{h} kv{hk} dh{dh}"
    mask = f"mask=block_diffusion block={block} band=skip"
    # (the forward two query heads a step, the backward one)
    assert rows == {
        f"bhtd fwd {shape} [hb2 bq512 bk512] stats=rows {mask}": 1,
        f"bhtd bwd {shape} [hb1 bq512 bk512] form=fused {mask}": 1}
    assert _calls(text) == {"attn.bhtd.fwd", "attn.bhtd.bwd"}
    assert f"{t},{t}]" not in text
