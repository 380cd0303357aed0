"""chip_smoke.py, debugged here and not on chip time.

(a) Without a TPU the script fails fast, naming the platform it found.
(b) Every phase function runs at toy sizes on the 8-virtual-device CPU
mesh: the same code the chip runs at full width, with the platform it
checks placement against set to "cpu" and the kernels interpreted.
"""
import json
import os
import subprocess
import sys

import jax

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402

_TOY_LM = dict(vocab=211, d_model=128, num_heads=4, num_layers=2,
               max_len=64, sessions=4, prompt_len=16, new_tokens=8,
               requests=4, platform="cpu")


def test_no_tpu_exits_nonzero_naming_the_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, None)
    assert "'platform': 'cpu'" in proc.stdout
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    # it stopped before building anything and printed no result
    assert '"ok"' not in proc.stdout and "phases" not in proc.stdout


def test_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    """What the chip check reads: the last line is a JSON object with
    exactly `ok` and `device` {platform, kind, count}; the per-phase
    record is the line before it."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke._report(False, dev, phases={"trainer": {"ok": False}},
                       wall_s=1.0)
    detail, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": False, "device": dev}
    assert detail.startswith("[smoke] result: ")
    full = json.loads(detail[len("[smoke] result: "):])
    assert full["phases"] == {"trainer": {"ok": False}}
    assert full["ok"] is False and full["device"] == dev


def test_trainer_phase_toy():
    res = chip_smoke.phase_trainer(depth=18, image=32, batch=4, steps=8,
                                   platform="cpu")
    assert res["ok"] and res["loss_last"] < res["loss_first"]


def test_server_phase_toy():
    meter = chip_smoke._CompileMeter()
    res = chip_smoke.phase_server(**_TOY_LM, meter=meter)
    assert res["ok"] and res["decode"]["completed"] == 4
    # XLA:CPU is where the README's stream identity was established
    assert res["streams_identical_to_generate"] == 4
    assert res["compiles_in_request_window"] == 0


def test_kernels_phase_toy(monkeypatch):
    from singa_tpu.ops import pallas_kernels as pk

    # the seq >= 1024 crossover gate is perf policy; drop it so the
    # toy sequence still routes through the (interpreted) kernel
    monkeypatch.setattr(pk, "_ATTN_MIN_SEQ", 0)
    res = chip_smoke.phase_kernels(
        xent_shapes=((16, 40),),
        attn_cases=((1, 2, 64, 32, "bfloat16"),
                    (1, 2, 64, 32, "float32")),
        decode_cases=((5, 2, 16, 256),),
        lm=(97, 64, 4, 2), lm_batch=2, lm_seq=64, lm_steps=3,
        interpret=True, platform="cpu")
    assert res["ok"] and res["lm_loss_last"] < res["lm_loss_first"]
    assert not pk.enabled()  # the phase put the tier switch back


def test_multichip_phase_toy():
    """Eight virtual devices, four used: the `mesh=` path a larger
    host takes (`ParallelPlan(data=4)` needs exactly four)."""
    assert jax.device_count() == 8
    res = chip_smoke.phase_multichip(
        chips=4, platform="cpu",
        trainer=dict(depth=18, image=32, batch=8, steps=4),
        server=dict(_TOY_LM, requests=1))
    assert res["trainer"]["data_parallel"] == 4
    assert res["server"]["device"] == str(jax.devices()[2])
    skipped = chip_smoke.phase_multichip(chips=16, platform="cpu")
    assert skipped == {"ok": True, "skipped": "8 device(s)"}
