"""chip_smoke.py off the chip: its phases driven at a tiny config on the
CPU (so the script cannot rot between chip runs), its refusal to pass
without a TPU, and the fallbacks this bring-up removed — decorative
places, a cache dir forced in code."""

import importlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as fluid
from paddle_tpu import flags, jax_cache, monitor
from paddle_tpu.parallel import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telemetry(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    yield
    flags.set_flags({"telemetry": False, "trace_dir": ""})


def tiny(**kw):
    return chip_smoke.transformer_base(
        src_vocab_size=61, trg_vocab_size=67, d_model=32, d_inner=64,
        n_head=2, n_layer=1, max_length=64, **kw)


# --- the phases, tiny, on the CPU ---

def test_kernel_phase_runs_the_kernels_through_the_interpreter():
    """Interpret mode is allowed only here, set by the test: the phase's
    compile/run/compare loop over the real kernel code (no dropout — the
    hardware PRNG has no CPU lowering)."""
    fa._INTERPRET = True
    try:
        rows = chip_smoke.kernel_phase(
            cases=(("bthd_small", 2, 32, True, 0.0, True),
                   ("bthd_small", 1, 32, False, 0.0, False)),
            h=2, dh=16)
    finally:
        fa._INTERPRET = False
    assert [r["family"] for r in rows] == ["bthd_small", "bthd_small"]
    assert set(rows[0]["rel_err"]) == {"out", "dq", "dk", "dv"}
    assert max(rows[0]["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL


def test_kernel_phase_fails_when_the_dispatch_leaves_the_family():
    # kernels off (CPU, no interpreter): every shape is "dense"
    with pytest.raises(chip_smoke.SmokeFailure, match="expected family"):
        chip_smoke.kernel_phase(
            cases=(("bthd_small", 1, 32, False, 0.0, False),), h=2, dh=16)


def test_moe_phase_runs_both_paths_of_the_layer(telemetry, monkeypatch):
    """The grouped matmuls' smoke at a size ``gmm_tile`` takes, through
    the interpreter: nine calls with a tile, nine through ragged_dot,
    and the two paths agree."""
    from paddle_tpu.parallel import grouped_matmul as gm

    monkeypatch.setattr(gm, "_INTERPRET", True)
    row = chip_smoke.moe_phase(tokens=256, d=128, d_ff=128, experts=4,
                               top_k=2)
    assert row["dispatch"] == {
        **{f"{p} m512 k128 n128 e4 [tm128 tk128 tn128]": 3
           for p in ("fwd", "bwd_dx", "bwd_dw")},
        **{f"{p} m512 k128 n128 e4": 3
           for p in ("fwd", "bwd_dx", "bwd_dw")}}
    assert set(row["rel_err"]) == {
        "out", "dx", "dsmoke_moe_router.w", "dsmoke_moe_gate.w",
        "dsmoke_moe_up.w", "dsmoke_moe_down.w"}
    assert max(row["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL


def test_moe_phase_fails_when_no_call_takes_a_tile(telemetry):
    # kernels off (CPU, no interpreter): all eighteen are ragged_dot
    with pytest.raises(chip_smoke.SmokeFailure, match="did not all take"):
        chip_smoke.moe_phase(tokens=256, d=128, d_ff=128, experts=4,
                             top_k=2)


def test_moe_held_phase_runs_the_layer_windowed_and_whole(telemetry,
                                                          monkeypatch):
    """A held layer at a size ``gmm_tile`` and ``sum_tile`` take,
    through the interpreter: 512 of 1024 rows expected live, windows of
    256, the two token-major sums the ``pairs.sum.*`` kernel's and every
    other pass ``windowed``, and the one-window form agrees."""
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.parallel import grouped_matmul as gm
    from paddle_tpu.parallel import pair_sum

    monkeypatch.setattr(gm, "_INTERPRET", True)
    monkeypatch.setattr(pair_sum, "_INTERPRET", True)
    row = chip_smoke.moe_held_phase(tokens=512, d=128, d_ff=128, experts=8,
                                    top_k=2, held=(2, 4), steps=1)
    assert row["window"] == 256 and row["rows"] == 1024
    assert 0.2 < row["live_share"] < 0.8
    assert len(row["passes"]) == 20 and all(
        " 1024 w" in k for k in row["passes"])
    assert sorted(k for k in row["passes"] if " windowed " not in k) == [
        f"{op} kernel 1024 w{w}" for op in (
            "moe_combine sum_pairs", "moe_dispatch_grad d_x")
        for w in (1024, 256)]
    assert set(row["step_ms"]) == {"windowed", "whole"}
    assert max(row["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL
    assert moe_ops.live_window(1024, 512) == 256     # put back


def test_moe_held_phase_fails_without_the_kernels(telemetry):
    # kernels off (CPU, no interpreter): all eighteen are ragged_dot
    with pytest.raises(chip_smoke.SmokeFailure, match="did not all take"):
        chip_smoke.moe_held_phase(tokens=512, d=128, d_ff=128, experts=8,
                                  top_k=2, held=(2, 4), steps=1)


def test_rope_phase_holds_the_program_to_the_kernels(telemetry, monkeypatch):
    """The phase at a short sequence and 4 / 2 heads through the
    interpreter: the one-layer Program lowers one ``rope.fwd`` and one
    ``rope.bwd`` from token-major q and k, and the kernels agree with
    ``_rotate`` and its vjp to bf16 rounding."""
    from paddle_tpu.parallel import rope

    monkeypatch.setattr(rope, "_INTERPRET", True)
    row = chip_smoke.rope_phase(seq=64, heads=(4, 2),
                                moe_num_primary_experts=4)
    assert row["lowered"] == {"kernel fwd bthd 128": 1,
                              "kernel bwd bthd 128": 1}
    assert row["tile"] == [64, 4] and row["kernel_ms"] == {}
    assert max(row["rel_err"].values()) <= 2.0 ** -7


def test_rope_phase_fails_without_the_kernels(telemetry):
    # kernels off (CPU, no interpreter): XLA's transpose and _rotate
    with pytest.raises(chip_smoke.SmokeFailure, match="none through XLA"):
        chip_smoke.rope_phase(seq=64, heads=(4, 2),
                              moe_num_primary_experts=4)


def test_loss_head_phase_holds_the_program_to_one_call_each_way(telemetry):
    """The phase at 2 x 64 tokens, 32 wide, into 384 columns, compiled
    for the CPU (whose temporaries are not held): the head's step lowers
    one hard-label call each way with no gradient of Softmax, and
    trains."""
    row = chip_smoke.loss_head_phase(batch=2, seq=64, width=32, vocab=384,
                                     steps=2, temp_share=None)
    assert row["lowered"] == {"hard fwd 0": 1, "hard bwd 0": 1}
    assert row["shape"] == [128, 32, 384] and row["temp_gb"] >= 0
    assert np.isfinite(row["loss"]).all()


@pytest.mark.parametrize("fault, match", [
    ("silent", "one hard-label call each way"),
    ("temporaries", "have room for a float32"),
])
def test_loss_head_phase_fails(telemetry, monkeypatch, fault, match):
    from paddle_tpu.ops import nn_ops

    if fault == "silent":   # the op lowered without saying so
        monkeypatch.setattr(nn_ops, "_note_loss_head", lambda *a, **k: None)
    with pytest.raises(chip_smoke.SmokeFailure, match=match):
        chip_smoke.loss_head_phase(
            batch=2, seq=64, width=32, vocab=384, steps=1,
            temp_share=None if fault == "silent" else 1e-3)


# --- the phases that lower a cell: one run a phase, shared ---

GDN_TINY = dict(
    vocab_size=50, hidden_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=128, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_num_key_heads=1,
    linear_num_value_heads=2, moe_intermediate_size=128,
    shared_expert_intermediate_size=128, num_experts=8,
    held_experts=(0, 4), num_experts_per_tok=2)
MLA_TINY = dict(
    vocab_size=50, hidden_size=128, num_hidden_layers=2,
    intermediate_size=128, num_attention_heads=1, q_lora_rank=32,
    kv_lora_rank=32, moe_intermediate_size=128, n_routed_experts=8,
    held_experts=(0, 4), num_experts_per_tok=2)
SSM_TINY = dict(
    vocab_size=50, hidden_size=512, num_attention_heads=8,
    num_key_value_heads=4, intermediate_size=128, sliding_window=128)
MAMBA2_TINY = dict(
    vocab_size=50, hidden_size=128, mamba_num_heads=4, n_groups=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    moe_intermediate_size=192, moe_shared_expert_intermediate_size=128,
    n_routed_experts=4, num_experts_per_tok=2, held_experts=(0, 2))
SCONV_TINY = dict(
    vocab_size=50, hidden_size=128, intermediate_size=256,
    num_attention_heads=2, num_key_value_heads=1, moe_intermediate_size=128,
    num_experts=4, num_experts_per_tok=2, held_experts=(0, 2))
BD_TINY = dict(
    vocab_size=50, mask_token_id=49, hidden_size=128, num_attention_heads=8,
    num_key_value_heads=1, head_dim=128, moe_intermediate_size=128,
    num_experts=4, num_experts_per_tok=2, held_experts=(0, 2),
    num_hidden_layers=2)
KEYE_TINY = dict(
    vocab_size=50, hidden_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=128, moe_intermediate_size=128,
    num_experts=4, num_experts_per_tok=2, held_experts=(0, 2),
    num_hidden_layers=2, indexer_num_heads=2, indexer_head_dim=64,
    topk=96)
KDA_TINY = dict(
    vocab_size=50, hidden_size=128, num_hidden_layers=3,
    intermediate_size=128, num_attention_heads=1, kv_lora_rank=32,
    moe_intermediate_size=128, num_experts=8, held_experts=(0, 4),
    num_experts_per_token=2,
    linear_attn_config={"kda_layers": [1, 3], "full_attn_layers": [2],
                        "head_dim": 128, "num_heads": 2,
                        "short_conv_kernel_size": 4})
XING4_TINY = dict(
    vocab_size=50, hidden_size=128, num_hidden_layers=2,
    intermediate_size=128, num_attention_heads=1, q_lora_rank=32,
    kv_lora_rank=32, moe_intermediate_size=128, n_routed_experts=8,
    held_experts=(0, 4), num_experts_per_tok=2, hc_sinkhorn_iters=3)
# phase: (the interpreters its kernels run through, the sequence its cut
# cell is lowered at, the rest of the phase's arguments, the cut config)
PHASES = {
    "gdn": ("grouped_matmul flash_attention gated_delta_rule causal_conv",
            512, dict(t_check=128, heads=(1, 2), width=16, gqa=(4, 2, 128),
                      conv_c=256), GDN_TINY),
    # (one head: a tile that batches heads lowers the backward as the
    # pair, which the phase refuses: the split-backward test below)
    "mla": ("grouped_matmul flash_attention", 512,
            dict(t_check=256, heads=1), MLA_TINY),
    "ssm": ("flash_attention causal_conv selective_scan", 512,
            dict(t_check=64), SSM_TINY),
    "mamba2": ("flash_attention causal_conv grouped_matmul mamba2_scan "
               "pair_sum", 512, dict(t_check=256), MAMBA2_TINY),
    "sconv": ("flash_attention causal_conv grouped_matmul pair_sum", 512,
              dict(t_check=256), SCONV_TINY),
    "bd": ("flash_attention grouped_matmul pair_sum rope", 512,
           dict(t_check=512, heads=(8, 1)), BD_TINY),
    "kda": ("flash_attention grouped_matmul pair_sum causal_conv "
            "gated_delta_rule", 512, dict(t_check=128), KDA_TINY),
    "xing4": ("flash_attention grouped_matmul pair_sum hc_mix", 1024,
              dict(t_check=1024), XING4_TINY),
    "keye": ("flash_attention grouped_matmul pair_sum rope dsa_score", 1024,
             dict(t_check=1024), KEYE_TINY),
}


@pytest.fixture(scope="module")
def phase_row(tmp_path_factory):
    """``phase_row(name)`` -> the row of the phase's ONE run in this
    module (its cut config through the interpreters, under telemetry):
    the ``holds`` test reads it, and the ``fails`` tests feed the
    phase's ``*_rows_hold`` an altered copy of its dispatch rows where
    they used to lower the cell again with one kernel off."""
    rows = {}

    def run(name):
        if name not in rows:
            interpreters, seq, args, tiny = PHASES[name]
            flags.set_flags({"telemetry": True, "trace_dir": str(
                tmp_path_factory.mktemp(name))})
            try:
                with pytest.MonkeyPatch.context() as patch:
                    for module in interpreters.split():
                        patch.setattr(importlib.import_module(
                            f"paddle_tpu.parallel.{module}"),
                            "_INTERPRET", True)
                    rows[name] = getattr(chip_smoke, f"{name}_phase")(
                        seq=seq, **args, **tiny)
            finally:
                flags.set_flags({"telemetry": False, "trace_dir": ""})
        return rows[name]

    return run


def fails(name, match, **altered):
    """The phase's check refuses its own run's rows with ``altered``
    ones in their place."""
    _, seq, _, tiny = PHASES[name]
    _, cfg = chip_smoke.cell(name, **tiny)
    # (a check that asks the kernel layer for a call's tile, as the
    # phase's own run did: through the interpreter)
    with pytest.raises(chip_smoke.SmokeFailure, match=match), \
            pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_INTERPRET", True)
        getattr(chip_smoke, f"{name}_rows_hold")(cfg, seq, altered)


def renamed(rows, old, new):
    """The dispatch rows with ``old`` in every key as ``new``: what the
    counter says where the call took the other implementation."""
    assert all(old in k for k in rows)
    return {k.replace(old, new): v for k, v in rows.items()}


def test_gdn_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters: a period of
    three delta-rule layers and one grouped-query attention layer
    lowers 3 + 3 delta-rule calls through the gdn.* kernels and 3 + 3
    causal convolutions through theirs, one attention call each way that names its 2 key/value heads, and 36
    grouped matmuls on tiles of 128 rows; on the device (here: the CPU,
    at a width that gets no tile) the chunkwise form agrees with the
    recurrence, the conv's kernels with the XLA form and the attention
    kernels with the dense composition."""
    row = phase_row("gdn")
    shape = "b1 t512 hk1 hv2 dk128 dv128 chunk64"
    assert row["gdn"] == {f"kernel fwd {shape}": 3,
                          f"kernel bwd {shape}": 3}
    assert row["conv"] == {"kernel fwd b1 t512 c512 taps4": 3,
                           "kernel bwd b1 t512 c512 taps4": 3}
    # (the forward two query heads a step over their group's ONE K / V
    # block, the backward one: bhtd_fwd_tile, bhtd_tile)
    assert sorted(row["attention"]) == [
        f"bhtd {d} b1 tq512 tk512 h4 kv2 dh128 [hb{hb} bq512 bk512]{form}"
        for d, hb, form in (("bwd", 1, " form=fused edge=256x256"),
                            ("fwd", 2, " stats=rows"))]
    assert row["attn_bwd_kernel_ms"] == {}      # (a trace needs the chip)
    assert sum(row["grouped_matmuls"].values()) == 36
    assert set(row["rel_err"]) == {
        "o", "dq", "dk", "dv", "dg", "dbeta", "conv_y", "conv_dx",
        "conv_dw", "attn_o", "attn_dq", "attn_dk", "attn_dv"}
    assert max(row["rel_err"].values()) < chip_smoke.GDN_REL_TOL
    assert max(v for k, v in row["rel_err"].items()
               if k.startswith("conv_")) < chip_smoke.KERNEL_REL_TOL


@pytest.mark.parametrize("why,overrides", [
    # the caller's fallback (gdn_impl="recurrent"): rows at chunk 1
    ("recurrent", {"kernel": "recurrent", "chunk64": "chunk1"}),
    ("chunked", {"kernel": "chunked"})])     # no tile: the kernels are off
def test_gdn_phase_fails_on_a_call_without_the_kernel(why, overrides,
                                                      phase_row):
    rows = phase_row("gdn")["gdn"]
    for old, new in overrides.items():
        rows = renamed(rows, old, new)
    fails("gdn", "none chunked, none recurrent",
          **dict(phase_row("gdn"), gdn=rows))


def test_gdn_phase_fails_on_a_convolution_without_the_kernel(phase_row):
    # the conv's kernels off: six XLA forms
    row = phase_row("gdn")
    fails("gdn", "none as XLA ops",
          **dict(row, conv=renamed(row["conv"], "kernel", "xla")))


def test_mla_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (the widths
    of a head are the model's: 192 over 128): the dense layer, one
    expert layer and the MTP module lower three attention calls each way
    at ``dk192 dv128`` with their tile, none dense, two sigmoid routers
    with a bias, and 18 grouped matmuls on tiles of 128 rows; on the
    device (here: the CPU) the kernels agree with the dense
    composition."""
    row = phase_row("mla")
    # (since PR 70 the kernels read QPe and KPe's one head themselves)
    assert row["attention"] == {
        f"bhtd {d} b1 tq512 tk512 h1 dk192 dv128 [hb1 bq256 bk256]{form}"
        " parts=own": 3
        for d, form in (("bwd", " form=fused"), ("fwd", " stats=rows"))}
    assert list(row["routers"]) == ["score=sigmoid bias=1 k=2 experts=8"]
    assert sum(row["grouped_matmuls"].values()) == 18
    # (``parts_o``: the forward given q and k in two parts, on the device)
    assert set(row["rel_err"]) == {"attn_o", "attn_dq", "attn_dk", "attn_dv",
                                   "parts_o"}
    assert max(row["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL
    # (the cut config's one head does not pair: one a forward step too)
    assert row["tile"] == "hb1 bq256 bk256"


def test_mla_phase_fails_on_a_split_backward_call(phase_row, monkeypatch):
    """Two heads in a step (a tile of a short sequence): the backward
    lowers as bwd_dq + bwd_dkv (the row the counter gives then, from
    ``bhtd_bwd_form``), and the phase says so."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    assert fa.bhtd_bwd_form(2, 512, 512, dh=192, dv=128, itemsize=2) == (
        "split")
    row = phase_row("mla")
    two = renamed(renamed(row["attention"], " h1 ", " h2 "), "[hb1", "[hb2")
    fails("mla", "none split", **dict(
        row, attention={k.replace("form=fused", "form=split"): v
                        for k, v in two.items()}))


def test_mla_phase_fails_on_a_latent_call_the_op_assembled(phase_row):
    # the fused kernels took the call, but with a wide q and k
    row = phase_row("mla")
    fails("mla", "assembled by the op", **dict(row, attention=renamed(
        row["attention"], "parts=own", "parts=assembled")))


def test_mla_phase_fails_on_a_dense_attention_call(phase_row):
    # the attention kernels off: every call is the dense composition
    row = phase_row("mla")
    fails("mla", "none dense", **dict(row, attention={
        k.replace("bhtd ", "dense ").split(" [")[0]: v
        for k, v in row["attention"].items()}))


@pytest.mark.parametrize("dropout,tol", [
    (0.1, chip_smoke.DP_DROPOUT_LOSS_REL_TOL),  # masks drawn per shard
    (0.0, chip_smoke.DP_LOSS_REL_TOL)])         # the same math
def test_train_then_data_parallel_phases(telemetry, dropout, tol):
    cfg = tiny(dropout=dropout)
    rep, losses = chip_smoke.train_phase(cfg, batch=8, seq=16, steps=2,
                                         window_steps=2)
    assert len(losses) == 2 and np.isfinite(rep["window_loss"])
    assert "cpu" in rep["executor_device"].lower()
    # off the chip the dispatch is observable too: everything went dense
    assert rep["dispatch"] and all(
        k.startswith("dense ") for k in rep["dispatch"])
    dp = chip_smoke.dp_phase(cfg, losses, batch=8, seq=16)
    assert dp["devices"] == len(jax.devices()) == 8
    assert dp["feed_shard_rows"] == 1
    assert dp["max_rel_diff"] <= dp["rel_tol"] == tol


def test_serve_phase(telemetry):
    rep = chip_smoke.serve_phase(
        tiny(dropout=0.0, label_smooth_eps=0.0), slots=2, src_len=8,
        max_len=12, max_new=3, src_lens=(8, 3, 5))
    assert rep["requests"] == 3 and rep["equal_to_solo_same_engine"]
    assert rep["compiles_after_warmup"] == 0
    # f32 on the CPU: the 2-slot and the 1-slot engine agree outright
    assert rep["equal_to_solo_1slot_highest"]
    assert rep["partings_vs_1slot_default"] == []
    assert rep["rel_logit_diff_vs_1slot"]["default"] < 1e-4
    # decode's tq=1 attention is named, not silent
    assert any(" tq1 " in k for k in rep["dispatch"])


def test_parting_names_the_first_differing_step_and_the_logit_gaps():
    a = [(5, 1.0), (7, 2.0), (9, 1.0)]
    assert chip_smoke._parting(a, list(a)) == (None, 0.0, None)
    first, before, at = chip_smoke._parting(
        a, [(5, 1.01), (8, 2.2), (9, 1.0)])
    assert first == 1
    assert before == pytest.approx(0.01 / 1.01)
    assert at == pytest.approx(0.2 / 2.2)
    # one stream ended on EOS where the other went on: they part there
    assert chip_smoke._parting(a, a[:2]) == (2, 0.0, None)


def test_chip_smoke_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr and "cpu" in out.stderr
    assert '"ok"' not in out.stdout


# --- the fallbacks that went ---

def test_explicit_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="TPUPlace.*default backend"):
        fluid.Executor(fluid.TPUPlace(0))
    exe = fluid.Executor()  # no place: jax's default device, recorded
    assert repr(exe.place) == "CPUPlace" and exe.device.platform == "cpu"


def test_mesh_attention_names_replicated_axes_and_refuses_uneven_batch(
        telemetry):
    """ops/attention_ops._on_mesh (a stand-in for the Pallas call: the
    interpreter cannot run one inside a shard_map): under dp x tp the
    batch splits over the data axis, each shard gets its first GLOBAL
    row, and the model axis repeating the call is on the record; a batch
    the data axis cannot split raises instead of running n times."""
    import jax.numpy as jnp

    from paddle_tpu import parallel
    from paddle_tpu.core import interp
    from paddle_tpu.ops import attention_ops

    mesh = parallel.create_mesh({"data": 2, "model": 2},
                                devices=jax.devices()[:4])
    strategy = parallel.DistributedStrategy(
        mesh, "data", parallel.transformer_rules("model"))

    def kernel(q, bias, seed):
        return q + seed[1].astype(q.dtype)  # seed = [seed, first row]

    def call(q):
        return attention_ops._on_mesh(
            kernel, (q, None), None, "bthd_small", "fwd",
            (q.shape[0], 8, 8, 2, 16))

    tok = interp.set_amp_active(False)  # as inside a block being lowered
    try:
        with interp.spmd_ctx_scope(strategy):
            out = jax.jit(call)(jnp.zeros((4, 8, 2, 16)))
            with pytest.raises(ValueError, match="does not split"):
                jax.jit(call)(jnp.zeros((3, 8, 2, 16)))
    finally:
        interp._AMP_ACTIVE.reset(tok)
    assert np.asarray(out[:, 0, 0, 0]).tolist() == [0, 0, 2, 2]
    assert attention_ops.dispatch_counts() == {
        "bthd_small fwd b2 tq8 tk8 h2 dh16 replicated_over=model": 1}


def test_dispatch_rows_name_the_bhtd_tile_and_the_keys_stay(telemetry,
                                                            monkeypatch):
    """pt_attention_dispatch_total: a ``bhtd`` row carries the tile its
    shape takes (label ``tile``); the other families' is empty, and
    ``dispatch_counts()`` is keyed as it was (family pass shape)."""
    from paddle_tpu import monitor
    from paddle_tpu.core import interp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.parallel import flash_attention as fa

    monkeypatch.setattr(fa, "_INTERPRET", True)   # the kernels take calls
    tok = interp.set_amp_active(False)  # as inside a block being lowered
    try:
        attention_ops._note_dispatch("bhtd", "fwd", (2, 4096, 4096, 16, 128))
        attention_ops._note_dispatch("bhtd", "bwd", (2, 1024, 1024, 2, 64))
        attention_ops._note_dispatch("bthd_small", "fwd", (64, 256, 256, 8, 64))
    finally:
        interp._AMP_ACTIVE.reset(tok)
    rows = {(r["labels"]["family"], r["labels"]["pass"]): r["labels"]["tile"]
            for r in monitor.snapshot()["pt_attention_dispatch_total"]["values"]}
    # (a forward row carries the forward's own tile, bhtd_fwd_tile: two
    # of the 16 heads a step; a backward row bhtd_tile's)
    assert rows == {("bhtd", "fwd"): "hb2 bq512 bk512",
                    ("bhtd", "bwd"): "hb2 bq256 bk256",
                    ("bthd_small", "fwd"): ""}
    assert attention_ops.dispatch_counts() == {
        "bhtd fwd b2 tq4096 tk4096 h16 dh128": 1,
        "bhtd bwd b2 tq1024 tk1024 h2 dh64": 1,
        "bthd_small fwd b64 tq256 tk256 h8 dh64": 1}
    assert attention_ops.dispatch_counts(tiles=True) == {
        "bhtd fwd b2 tq4096 tk4096 h16 dh128 [hb2 bq512 bk512]": 1,
        "bhtd bwd b2 tq1024 tk1024 h2 dh64 [hb2 bq256 bk256]": 1,
        "bthd_small fwd b64 tq256 tk256 h8 dh64": 1}


def test_rope_rows_that_differ_in_scaling_alone_add_up(telemetry):
    """``rope_dispatch`` leaves ``scaling`` out of its keys: lfm2's plain
    calls and xing4's yarn calls at one head width are one key, and the
    key's count is their sum (the last row alone read no new call in
    ``xing4_phase`` behind ``sconv_phase`` on the chip, PR 68)."""
    from paddle_tpu.ops import attention_ops

    row = {"impl": "xla", "pass": "fwd", "layout": "bthd", "dh": "64"}
    attention_ops._M_ROPE.inc(5, labels=dict(row, scaling="yarn"))
    attention_ops._M_ROPE.inc(labels=dict(row, scaling="none"))
    assert chip_smoke.rope_dispatch() == {"xla fwd bthd 64": 6}


@pytest.mark.parametrize("case", ["env", "default"])
def test_jax_cache_placement(case, tmp_path):
    """One rule (jax_cache.configure): a placed JAX_COMPILATION_CACHE_DIR
    wins and NOTHING is set in code, the write threshold included; else
    the one fixed directory in the checkout, with the 1 s threshold."""
    placed = str(tmp_path / "placed") if case == "env" else None
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    if placed:
        env.update(JAX_COMPILATION_CACHE_DIR=placed,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0.25")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "before = jax.config.jax_compilation_cache_dir\n"
         "from paddle_tpu import jax_cache\n"
         "print(repr((before, jax_cache.configure(), "
         "jax.config.jax_compilation_cache_dir, "
         "jax.config.jax_persistent_cache_min_compile_time_secs)))"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-800:]
    before, used, after, threshold = eval(out.stdout.strip().splitlines()[-1])
    if placed:
        assert (before, used, after, threshold) == (
            placed, placed, placed, 0.25)
    else:
        assert before is None
        assert used == after == jax_cache.JAX_CACHE_DIR
        assert used == os.path.join(REPO, ".cache", "jax")
        assert threshold == 1.0


def test_ssm_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (heads of 64
    over values of 128 and a state of 16 are the model's; 1024 channels):
    layers 14-19 lower two selective scans and two convolutions each way
    through their kernels and six attention calls each way at dk64
    dv128, the window layer's two with their band, every backward one
    call; on the device (here: the CPU) the scan's and the convolution's
    kernels agree with the XLA writings."""
    row = phase_row("ssm")
    assert row["selective_scans"] == {
        f"kernel {d} b1 t512 e1024 n16 chunk128": 2 for d in ("fwd", "bwd")}
    assert row["convolutions"] == {
        f"kernel {d} b1 t512 c1024 taps4": 2 for d in ("fwd", "bwd")}
    attn = row["attention"]
    assert sum(attn.values()) == 12 and all(
        k.startswith("bhtd ") and " h4 kv2 dk64 dv128" in k for k in attn)
    assert sum(v for k, v in attn.items() if " w128" in k) == 4
    assert all(k.endswith(" form=fused edge=256x256") for k in attn
               if " bwd " in k)
    assert row["kernel_ms"] == {}               # (a trace needs the chip)
    assert set(row["rel_err"]) == {
        "scan Out", *(f"scan GRAD::{s}" for s in (
            "X", "Dt", "A", "B", "C", "D", "Z", "DtBias")),
        "conv Y", "conv GRAD::X", "conv GRAD::W", "conv GRAD::Bias"}
    assert max(row["rel_err"].values()) < 2e-2


def test_ssm_phase_fails_on_a_scan_without_the_kernel(phase_row):
    # the scan's kernels off: both calls are the chunked XLA form (at its
    # own chunk), and the phase says so
    row = phase_row("ssm")
    fails("ssm", "on the ssm.scan kernels", **dict(
        row, selective_scans=renamed(renamed(
            row["selective_scans"], "kernel", "chunked"),
            "chunk128", "chunk64")))


def test_mamba2_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (heads of 64
    over a state of 128 in chunks of 128 are the model's; experts of 192
    = 1.5 lane tiles as 1856 = 14.5): blocks 34-42 lower four scans and
    four convolutions each way through their kernels, 24 grouped matmuls
    each on a tile and one attention call each way, its backward one
    call; on the device (here: the CPU) the scan's kernels agree with
    the XLA writing and the grouped matmuls with ragged_dot."""
    row = phase_row("mamba2")
    assert row["mamba2_scans"] == {
        f"kernel {d} b1 t512 h4 p64 g2 n128 chunk128": 4
        for d in ("fwd", "bwd")}
    assert row["convolutions"] == {
        f"kernel {d} b1 t512 c768 taps4": 4 for d in ("fwd", "bwd")}
    assert sum(row["grouped_matmuls"].values()) == 24
    # (192 whole as a contraction, one block of 256 over the edge as a
    # width)
    assert all("tk192" in k or "tn256" in k for k in row["grouped_matmuls"])
    attn = row["attention"]
    assert sum(attn.values()) == 2 and all(
        k.startswith("bhtd ") and " h4 kv2 " in k for k in attn)
    assert all(k.endswith(" form=fused edge=256x256") for k in attn
               if " bwd " in k)
    assert row["kernel_ms"] == {}               # (a trace needs the chip)
    assert set(row["rel_err"]) == {
        "scan Out", *(f"scan GRAD::{s}" for s in (
            "X", "Dt", "ALog", "B", "C", "D", "DtBias")),
        "gmm y", "gmm dx", "gmm dw"}
    assert max(row["rel_err"].values()) < 2e-2


def test_mamba2_phase_fails_on_a_scan_without_the_kernel(phase_row):
    # the scan's kernels off: the calls are the chunked XLA form, and the
    # phase says so
    row = phase_row("mamba2")
    fails("mamba2", "on the mamba2.chunk kernels", **dict(
        row, mamba2_scans=renamed(row["mamba2_scans"], "kernel", "chunked")))


def test_sconv_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (heads of 64
    and three taps are the model's): layers 1-5 lower four gated
    convolutions each way through ``sconv.gated.*`` and one attention
    call each way, its backward one call; the rotary embedding of a
    head of 64 is XLA's; on the device (here: the CPU) the kernels agree
    with the composition."""
    row = phase_row("sconv")
    assert row["convolutions"] == {
        f"kernel {d} b1 t512 c128 taps3 gated": 4 for d in ("fwd", "bwd")}
    attn = row["attention"]
    assert sum(attn.values()) == 2 and all(
        k.startswith("bhtd ") and " h2 kv1 dh64 " in k for k in attn)
    assert all(k.endswith(" form=fused edge=256x256") for k in attn
               if " bwd " in k)
    assert row["rotary_embeddings"] == {"xla fwd bthd 64": 1,
                                        "xla bwd bthd 64": 1}
    assert row["kernel_ms"] == {}               # (a trace needs the chip)
    assert set(row["rel_err"]) == {"Y", "dB", "dC", "du", "dW"}
    assert max(row["rel_err"].values()) < 2e-2


def test_sconv_phase_fails_on_a_convolution_without_the_kernel(phase_row):
    # the convolution's kernels off: the calls are the composition in XLA
    # ops, and the phase says so
    row = phase_row("sconv")
    fails("sconv", "on the sconv.gated kernels", **dict(
        row, convolutions=renamed(row["convolutions"], "kernel", "xla")))


def test_bd_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (8 query heads
    a key/value head of 128 and blocks of 4 are the model's): every
    layer lowers one block-masked attention call each way over the 2 x
    512 positions in the BHTD kernels (``band=skip``), the backward one
    call, the logsumexp in rows, and one rotary embedding each way on
    the ``rope.*`` kernels with the heads' gains (``norm=head``); on the
    device (here: the CPU) the kernels agree with the dense
    composition."""
    row = phase_row("bd")
    shape = "b1 tq1024 tk1024 h8 kv1 dh128"
    mask = "mask=block_diffusion block=4 band=skip"
    assert row["attention"] == {
        f"bhtd fwd {shape} [hb2 bq512 bk512] stats=rows {mask}": 2,
        f"bhtd bwd {shape} [hb1 bq512 bk512] form=fused {mask}": 2}
    assert row["rotary_embeddings"] == {"kernel fwd bthd 128 norm=head": 2,
                                        "kernel bwd bthd 128 norm=head": 2}
    assert row["kernel_ms"] == {}               # (a trace needs the chip)
    # one tile a half: the noised half's diagonal block, its clean block
    # and the clean half's diagonal block, each an edge worked on whole
    assert row["pairs"] == {"fwd": [3 * 512 * 512, 512 * 512 + 4 * 512],
                            "fused": [3 * 512 * 512, 512 * 512 + 4 * 512]}
    assert max(row["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL


def test_bd_phase_fails_on_a_block_masked_call_that_runs_dense(
        phase_row, monkeypatch):
    """A row whose half is no whole number of tiles (384) has no tile
    under the mask: its calls are the dense composition, and the phase
    says so of the rows the counter gives then."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    assert fa.bhtd_tile(8, 768, 768, dh=128, group=8,
                        block_diffusion=4) is None
    shape = "b1 tq1024 tk1024 h8 kv1 dh128"
    fails("bd", "none dense", **dict(phase_row("bd"), attention={
        f"dense {d} {shape} mask=block_diffusion block=4 band=dense": 2
        for d in ("fwd", "bwd")}))


def test_attention_pairs_of_the_cells_calls(monkeypatch):
    """chip_smoke prints, for the decoder cells' BHTD calls, the score
    pairs a head's steps compute against those the mask lets through:
    laguna's band of one block keeps two thirds in the backward at
    sub-tiles of 256 where whole edge blocks (the forward's) keep
    half."""
    from paddle_tpu.parallel import flash_attention as fa

    monkeypatch.setattr(fa, "_INTERPRET", True)
    pairs = chip_smoke.attention_pairs()
    assert set(pairs) == set(chip_smoke.ATTN_GEOMETRIES)
    sub = fa.edge_label((fa._EDGE_SUB,) * 2)
    assert all(row["edge"] == sub for row in pairs.values())
    band = pairs["laguna w512"]
    assert band["live"] == 4063488
    assert band["computed"] == {256: 6094848, 128: 5079040}[fa._EDGE_SUB]
    assert band["computed_fwd"] == 8126464
    assert all(0.5 < row["live_share"] <= 1.0 for row in pairs.values())


def test_kda_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (a head's 128
    features and the chunk of 64 are the model's): two KDA layers and a
    latent layer between them lower two delta-rule calls each way as
    ``kernel feature``, two convolutions each way on the kernels, one
    attention call each way at ``dk192 dv128`` with the fused backward
    and no rotary embedding; on the device (here: the CPU) the kernels
    agree with the float32 recurrence with G below -200 inside a chunk
    and with mild gates."""
    row = phase_row("kda")
    shape = "b1 t512 hk2 hv2 dk128 dv128 chunk64"
    assert row["kda"] == {f"kernel feature {d} {shape}": 2
                          for d in ("fwd", "bwd")}
    assert row["conv"] == {f"kernel {d} b1 t512 c768 taps4": 2
                           for d in ("fwd", "bwd")}
    assert row["attention"] == {
        f"bhtd {d} b1 tq512 tk512 h1 dk192 dv128 [hb1 bq256 bk256]{form}"
        " parts=own": 1
        for d, form in (("bwd", " form=fused"), ("fwd", " stats=rows"))}
    assert row["rotary_embeddings"] == {} and row["kda_kernel_ms"] == {}
    assert set(row["rel_err"]) == {
        f"{gates}.{n}" for gates in ("steep", "mild")
        for n in ("o", "dq", "dk", "dv", "dg", "dbeta")}
    assert max(row["rel_err"].values()) < 0.012


def test_kda_phase_fails_on_a_call_without_the_kernel(phase_row):
    # the rule's kernels off: the chunked XLA form
    row = phase_row("kda")
    fails("kda", "through the kda.rule", **dict(
        row, kda=renamed(row["kda"], "kernel", "chunked")))


def test_xing4_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (four streams,
    a head of 192 over 128 and the yarn table are the model's): a dense
    and an expert layer lower four mixes each way on the ``hc.mix.*``
    kernels with their reads and write-backs as XLA's ops, one attention
    call a layer each way at ``dk192 dv128`` with the fused backward, a
    rotary embedding a layer each way, a sigmoid router with a selection
    bias; on the device (here: the CPU) the kernels agree with XLA's ops
    with logits beyond the clamp among them."""
    row = phase_row("xing4")
    assert row["hc"] == {f"{impl} {op} {d}": 4 for op, impl in (
        ("mix", "kernel"), ("pre", "xla"), ("post", "xla"))
        for d in ("fwd", "bwd")}
    assert sum(row["attention"].values()) == 4 and all(
        " dk192 dv128 [" in k for k in row["attention"])
    assert sum(row["rotary_embeddings"].values()) == 4
    assert list(row["routers"]) == ["score=sigmoid bias=1 k=2 experts=8"]
    assert set(row["rel_err"]) == {"h_res", "dz"}
    assert max(row["rel_err"].values()) < 1e-4
    assert row["columns_off_one"] < 1e-5 and row["hc_kernel_ms"] == {}


def test_keye_phase_holds_the_lowered_cell_to_its_dispatch_rows(phase_row):
    """The phase at a cut config through the interpreters (8 / 2 heads
    of 128, an index head of 64 and the fed positions are the model's):
    every layer lowers a ``dsa_select`` on ``dsa.score.fwd``, a
    ``dsa_index_loss`` on ``dsa.loss.bwd`` and its grad op, one attention
    call each way under the selection in the BHTD kernels
    (``sel=operand``: the tile's blocks are the selection's chunks of
    512), the backward one call, and two rotary embeddings each way; on the device (here: the
    CPU) the kernel's selection is XLA's and the kernels under it agree
    with the dense composition."""
    row = phase_row("keye")
    shape = "b1 t1024 hI2 dI64"
    assert row["dsa"] == {
        f"kernel select fwd {shape} k96 cq512 ck512": 2,
        f"kernel loss fwd {shape} k0 cq512 ck512": 2,
        f"xla loss bwd {shape} k0 cq512 ck512": 2}
    call = "b1 tq1024 tk1024 h8 kv2 dh128"
    assert row["attention"] == {
        f"bhtd fwd {call} [hb2 bq512 bk512] stats=rows sel=operand": 2,
        f"bhtd bwd {call} [hb1 bq512 bk512] form=fused edge=256x256 "
        "sel=operand": 2}
    assert row["rotary_embeddings"] == {
        "kernel fwd bthd 128 norm=head": 2, "kernel bwd bthd 128 norm=head": 2,
        "xla fwd bthd 64": 2, "xla bwd bthd 64": 2}
    assert row["kernel_ms"] == {}               # (a trace needs the chip)
    # two chunks a layer, dsa.topk.fwd's passes over each one's prefix
    assert row["topk_columns"] == {"causal": 3072, "walked": 3072}
    assert row["selection_agrees"] > 0.9999 and row["top_k_agrees"] == 1.0
    assert max(row["rel_err"].values()) < chip_smoke.KERNEL_REL_TOL


def test_keye_phase_fails_on_a_selection_that_runs_dense(phase_row,
                                                         monkeypatch):
    # the calls under a selection as the dense composition, and the
    # scores as XLA's ops where score_tile takes the call: the counters'
    # rows say so and the phase refuses them
    from paddle_tpu.parallel import dsa_score

    monkeypatch.setattr(dsa_score, "_INTERPRET", True)
    row = phase_row("keye")
    fails("keye", "none dense", **dict(row, attention={
        f"dense {d} b1 tq1024 tk1024 h8 kv2 dh128 sel=dense": 2
        for d in ("fwd", "bwd")}))
    fails("keye", "as kernel", **dict(row, dsa={
        k.replace("kernel select", "xla select"): v
        for k, v in row["dsa"].items()}))
    # and every chunk walked at the row's whole width
    fails("keye", "score columns", **dict(row, topk_columns={
        "causal": 3072, "walked": 4096}))


@pytest.mark.parametrize("name", ["bd", "keye"])
def test_phase_fails_on_a_forward_back_at_one_head_a_step(name, phase_row,
                                                          monkeypatch):
    """The forward rows of a lowered cell carry ``bhtd_fwd_tile``'s tile
    (two query heads a step over their group's ONE K / V block): a row
    at the backward's one head a step, what a silent fall back would
    count, is refused; so is a backward row that left one head."""
    from paddle_tpu.parallel import dsa_score

    # (keye's rows name the indexer's kernels, which score_tile gives)
    monkeypatch.setattr(dsa_score, "_INTERPRET", True)
    row = phase_row(name)
    fwd = {k: v for k, v in row["attention"].items() if " fwd " in k}
    bwd = {k: v for k, v in row["attention"].items() if " bwd " in k}
    assert fwd and all("[hb2 " in k for k in fwd)
    fails(name, "bhtd fwd call on the tile hb2 bq512 bk512",
          **dict(row, attention={**renamed(fwd, "[hb2", "[hb1"), **bwd}))
    fails(name, "bhtd bwd call on the tile hb1 bq512 bk512",
          **dict(row, attention={**fwd, **renamed(bwd, "[hb1", "[hb2")}))


def test_xing4_phase_fails_on_a_mix_without_the_kernel(phase_row,
                                                       monkeypatch):
    # the mixes lowered as XLA's ops where mix_tile gives the call a
    # tile: the counter's rows say so and the phase refuses them
    from paddle_tpu.parallel import hc_mix

    monkeypatch.setattr(hc_mix, "_INTERPRET", True)
    row = phase_row("xing4")
    fails("xing4", "as kernel", **dict(row, hc={
        k.replace("kernel mix", "xla mix"): v for k, v in row["hc"].items()}))
