"""CPU rehearsal of ``chip_smoke.py``: guards the script's control flow and
its refusal to report ``ok`` anywhere but on a TPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one CPU device, like a one-chip machine
    out = subprocess.run(
        [sys.executable, SMOKE, "--logdir", str(tmp_path / "logs")] + args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return out, lines


@pytest.mark.e2e
def test_rehearsal_serves_every_request_then_refuses_ok(tmp_path):
    out, lines = _run(["--rehearse"], tmp_path, 600)
    by_phase = {ln.get("phase"): ln for ln in lines}
    # every phase ran to its end and passed ...
    assert by_phase["kernel"]["ok"], by_phase["kernel"]
    assert by_phase["transport"]["ok"]
    serve = by_phase["serve"]
    assert serve["ok"], serve.get("failures")
    assert len(serve["requests"]) == 11
    for r in serve["requests"]:
        assert r["done"] and not r["errors"], r
        assert r["usage"]["completion_tokens"] == r["max_tokens"]
    assert serve["worker_exit_code_after_drain"] == 0
    # ... the control plane never touched JAX ...
    for who in ("store", "frontend"):
        assert not serve["accelerator_holders"][who]["jaxlib_mapped"]
    # ... the kernel was interpreted, and said so, with the tile it was
    # traced with (the default resolved: 256 keys a step of the KV walk) ...
    assert serve["attention_traced"]["decode"] == {
        "impl": "pallas", "interpret": True, "tile": [1, 256]}
    # ... and for exactly that reason — the platform — there is no ok
    assert out.returncode == 3, out.stderr[-2000:]
    assert "not a chip run" in out.stderr
    assert serve["device"]["platform"] == "cpu"
    assert not any(ln.get("ok") is True and "phase" not in ln
                   for ln in lines)
    assert "phase" in lines[-1]  # the last line is a phase, not the verdict


@pytest.mark.e2e
def test_without_rehearse_no_tpu_fails_fast_and_prints_no_result(tmp_path):
    out, lines = _run([], tmp_path, 120)
    assert out.returncode == 1
    assert not any(ln.get("ok") is True for ln in lines)
    assert "no TPU" in json.dumps(lines)


def test_alone_in_a_directory_it_fails(tmp_path):
    # the driver also runs the script in a directory that holds nothing else
    # of the repo: non-zero, no result
    alone = tmp_path / "alone"
    alone.mkdir()
    (alone / "chip_smoke.py").write_text(open(SMOKE).read())
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=120, cwd=alone, env=dict(os.environ))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
