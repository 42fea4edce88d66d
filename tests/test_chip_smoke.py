"""chip_smoke.py's legs at tiny sizes on the CPU — the same functions
``main()`` runs at full width on the chip, with ``platform="cpu"``
(Pallas kernels in interpret mode) — and the script's refusal to run
without a TPU."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, _ROOT)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(_ROOT)


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                 # no result line
    reason = proc.stderr.strip().splitlines()
    assert len(reason) == 1 and "no TPU" in reason[0], proc.stderr


def test_leg_live_runtime(smoke):
    facts = smoke.leg_live_runtime(platform="cpu", num_nodes=3,
                                   num_tasks=600, remote_tasks=40)
    assert len(facts["solvers"]) >= 2
    assert all(s["device_errors"] == 0 and s["fallbacks"] == 0
               for s in facts["solvers"])


def test_leg_scheduler_kernel(smoke):
    facts = smoke.leg_scheduler_kernel(
        platform="cpu", num_tasks=2000, classes=16, nodes=100,
        resources=8, live_ticks=2)
    assert facts["fused_equals_scan"] and facts["placed_tick0"] > 0
    assert facts["tick_equals_entry_point"]
    assert facts["entry_path"] == "single/jnp"    # the CPU's choice


def test_leg_trainer(smoke):
    facts = smoke.leg_trainer(
        platform="cpu", batch=2, seq=128, steps=3, dtype="float32",
        flash_tol=1e-4,
        model=dict(vocab_size=256, d_model=128, n_layers=2, n_heads=2,
                   d_ff=256, max_seq_len=128, remat=True))
    assert facts["losses"][-1] < facts["losses"][0]
    assert not facts["flash_in_step"]             # reference off the chip
    assert not facts["flash_bwd_in_step"]
    assert facts["flash_fwd_calls_in_step"] == 0  # no Mosaic call to count
    assert facts["flash_bwd_vs_grad_of_full_max_rel_err"] <= 1e-4


def test_leg_latent_trainer(smoke):
    facts = smoke.leg_latent_trainer(
        platform="cpu", batch=2, seq=128, steps=2, dtype="float32",
        flash_tol=1e-4,
        model=dict(smoke.LATENT_MODEL, vocab_size=256, d_model=64, n_heads=2,
                   d_ff=96, max_seq_len=128, moe_experts=8, moe_top_k=2,
                   moe_experts_held=(2, 4), moe_d_ff=32, moe_shared_width=32,
                   mla=dict(q_lora_rank=48, kv_lora_rank=32,
                            qk_nope_head_dim=32, qk_rope_head_dim=16,
                            v_head_dim=32)))
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["flash_fwd_calls_in_step"] == 0  # no Mosaic call to count
    assert facts["counters"]["moe_dropped_choices"] == 0.0
    assert facts["counters"]["mtp_loss"] > 0
    assert facts["ragged_dot_calls_a_layer"] == 0  # no custom call here
    assert facts["latent_flash_bwd_max_rel_err"] <= 1e-4


def test_leg_hybrid_trainer(smoke):
    facts = smoke.leg_hybrid_trainer(
        platform="cpu", batch=2, seq=128, steps=2, dtype="float32",
        rule_tol=1e-4,
        model=dict(smoke.HYBRID_MODEL, vocab_size=256, d_model=64, n_heads=2,
                   head_dim=32, d_ff=32, max_seq_len=128, rotary_dim=8,
                   moe_experts=8, moe_top_k=2, moe_experts_held=(2, 4),
                   moe_shared_width=32,
                   gdn=dict(num_key_heads=2, num_value_heads=4,
                            key_head_dim=16, value_head_dim=16,
                            conv_kernel=4, chunk=32)))
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["gated_delta_calls_in_step"] == [0, 0]   # no Mosaic call
    assert facts["causal_conv_calls_in_step"] == [0, 0]
    assert facts["ragged_dot_calls_a_layer"] == 0
    assert facts["counters"]["gdn_conv_fallback_passes"] == 1.0
    # 64 of every 96 columns a key head: no shape of the kernels'
    assert facts["causal_conv_max_rel_err"] is None
    assert facts["counters"]["moe_dropped_choices"] == 0.0
    assert facts["gated_delta_bwd_max_rel_err"] <= 1e-4


def test_leg_sambay_trainer(smoke):
    facts = smoke.leg_sambay_trainer(
        platform="cpu", batch=1, seq=128, steps=2, dtype="float32",
        scan_tol=1e-4, flash_tol=1e-4,
        model=dict(smoke.SAMBAY_MODEL, vocab_size=256, d_model=64, n_heads=8,
                   n_kv_heads=4, d_ff=96, max_seq_len=128,
                   mamba=dict(d_inner=1024, d_state=4, d_conv=4, dt_rank=4,
                              chunk=32)))
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["selective_scan_calls_in_step"] == [0, 0]  # no Mosaic call
    assert facts["counters"]["ssm_scan_fallback_passes"] == 1.0
    assert facts["selective_scan_bwd_max_rel_err"] <= 1e-4
    assert facts["window_flash_max_rel_err"] <= 1e-4


def test_leg_sharded_solve(smoke):
    facts = smoke.leg_sharded_solve(platform="cpu", nodes=4096, classes=8,
                                    num_tasks=5000, tick_specs=256)
    assert facts["sharded_equals_single"] and facts["devices"] == 8


def test_leg_model_parallel(smoke):
    facts = smoke.leg_model_parallel(platform="cpu", devices=4)
    assert facts["mesh"] == {"dp": 1, "sp": 2, "tp": 2, "ep": 2, "pp": 2}
    assert facts["param_devices"] == [0, 1, 2, 3]


def test_a_failed_check_fails_the_process(smoke):
    # Told to find a TPU, the leg must not accept what the CPU picked.
    with pytest.raises(SystemExit,
                       match="FAILED: solve_matrices took single/jnp"):
        smoke.leg_scheduler_kernel(
            platform="tpu", num_tasks=200, classes=8, nodes=16,
            resources=8, live_ticks=0)
