"""chip_smoke.py: the script the driver runs on the chip.  Here, on the
CPU, it must refuse — quickly, naming the platform, with no result
line — and its nine legs must run at toy widths through the explicit
dry run, whose result line can never be read as a pass on the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=dict(os.environ), cwd=REPO, capture_output=True, text=True,
        timeout=timeout)


def test_chip_smoke_refuses_the_cpu_and_names_it():
    proc = _run(timeout=60)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform 'cpu'" in proc.stderr, \
        proc.stderr[-2000:]
    assert proc.stdout.strip() == "", proc.stdout


@pytest.mark.slow
def test_chip_smoke_dry_run_reaches_every_leg():
    proc = _run("--dry-run", timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # The last line has the driver's two keys and no others, and is
    # never mistakable for a pass on the chip.
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert verdict["device"]["count"] == 8
    out = report
    assert out["dry_run"] == "passed" and out["platform"] == "cpu"
    assert out["n"] == 8 and out["claim"] is None
    legs = out["legs"]
    assert set(legs) == {"A_resnet_dp", "B_lm_pallas", "C_eager",
                         "D_serve", "E_latent_moe", "F_hybrid_ssm",
                         "G_shortcut_moe", "H_mamba2_hybrid",
                         "I_latent_paged_attn"}
    # The dry run forces the stream schedule (auto is the one-program
    # step on a mesh one process owns): no other leg runs it.
    assert legs["A_resnet_dp"]["schedule"] == "stream"
    assert legs["A_resnet_dp"]["overlap_fallbacks"] == 0
    assert legs["A_resnet_dp"]["overlap_buckets"] > 0
    assert {"dp8", "long", "dp4xtp2", "flash_resident",
            "flash_streaming"} <= set(legs["B_lm_pallas"])
    assert legs["C_eager"]["size"] == 8
    assert legs["C_eager"]["megakernel_launches"] >= 1
    assert legs["D_serve"]["prefix_hits"] >= 1
    assert legs["E_latent_moe"]["config"] == "tiny-axk1"
    assert max(legs["E_latent_moe"]["logit_rms_over_std"]) < 1e-4
    assert legs["E_latent_moe"]["pairs_on_held_experts"] > 0
    assert legs["F_hybrid_ssm"]["config"] == "tiny-phi4flash"
    assert max(legs["F_hybrid_ssm"]["logit_rms_over_std"]) < 1e-4
    assert legs["F_hybrid_ssm"]["shared_kv_tokens"] > 0
    assert legs["G_shortcut_moe"]["config"] == "tiny-longcat"
    assert max(legs["G_shortcut_moe"]["logit_rms_over_std"]) < 1e-4
    assert 0 < legs["G_shortcut_moe"]["pairs_on_zero_experts"] < legs[
        "G_shortcut_moe"]["pairs_routed"]
    assert legs["H_mamba2_hybrid"]["config"] == "tiny-granite4h"
    assert max(legs["H_mamba2_hybrid"]["logit_rms_over_std"]) < 1e-4
    assert legs["H_mamba2_hybrid"]["state_bytes_moved"] > 0
    toy = legs["I_latent_paged_attn"]["toy"]
    assert toy["alive"] == 3 and toy["max_err_over_max"] < 1e-5
    assert toy["live_tokens"] <= toy["kernel_tokens_a_layer"]


# ---------------------------------------------------------------------------
# One process for each chip: the launcher on a TPU host
# ---------------------------------------------------------------------------

def test_launcher_binds_one_chip_per_worker():
    from horovod_tpu import run

    env = run._tpu_worker_env(2, [8476, 8477, 8478, 8479])
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["CLOUD_TPU_TASK_ID"] == "2"
    assert env["TPU_PROCESS_PORT"] == "8478"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
    assert env["TPU_PROCESS_ADDRESSES"].count("localhost:") == 4


@pytest.mark.parametrize("np_, chips", [(2, 4), (4, 1), (2, 8)])
def test_launcher_refuses_np_that_cannot_be_bound(monkeypatch, capsys,
                                                  np_, chips):
    """On a TPU host, -np N workers that cannot get one chip each would
    all open every chip: the launcher says so instead of starting."""
    from horovod_tpu import run

    monkeypatch.setattr(run, "_local_tpu_chips", lambda: chips)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(run, "_launch_once",
                        lambda *a, **k: pytest.fail("launched"))
    with pytest.raises(SystemExit) as e:
        run.main(["-np", str(np_), "train.py"])
    assert e.value.code != 0
    assert "device lock" in capsys.readouterr().err


def test_launcher_leaves_cpu_jobs_alone(monkeypatch):
    from horovod_tpu import run

    monkeypatch.setattr(run, "_local_tpu_chips", lambda: 4)
    seen = {}
    monkeypatch.setattr(run, "_launch_once",
                        lambda args, *a: seen.update(bind=args.bind_tpu)
                        or 0)
    assert run.main(["-np", "2", "--platform", "cpu", "train.py"]) == 0
    assert seen == {"bind": False}
    assert run.main(["-np", "4", "train.py"]) == 0  # JAX_PLATFORMS=cpu env
    assert seen == {"bind": False}
    monkeypatch.delenv("JAX_PLATFORMS")
    assert run.main(["-np", "4", "train.py"]) == 0
    assert seen == {"bind": True}
