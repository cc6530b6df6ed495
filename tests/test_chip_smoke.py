"""chip_smoke.py's contract with the driver, pinned without a chip.

The driver reads ONE thing: the last line of the script's stdout.  PR 22
was lost to that line's shape alone, so (a) the line builder, (b) the
script's behaviour where there is no TPU and (c) a tiny rehearsal of the
served phase — real server, real shell, real rpcs, on the CPU backend —
are all held to it here.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _assert_contract_shape(line: str) -> dict:
    assert "\n" not in line
    parsed = json.loads(line)
    assert set(parsed) == {"ok", "device"}
    assert set(parsed["device"]) == {"platform", "kind", "count"}
    assert isinstance(parsed["ok"], bool)
    assert isinstance(parsed["device"]["count"], int)
    return parsed


@pytest.mark.parametrize("ok,device", [
    (True, ("tpu", "TPU v5 lite", 1)),
    (False, ("cpu", "cpu", 1)),
    (False, ("", "", 0)),
    (True, ("tpu", "TPU v5 lite", 4)),
])
def test_final_line_has_exactly_the_contract_keys(ok, device):
    line = chip_smoke.final_line(ok, *device)
    parsed = _assert_contract_shape(line)
    assert parsed == {"ok": ok, "device": dict(
        zip(("platform", "kind", "count"), device))}
    assert line == json.dumps(parsed)  # no indent, one line


def _run(argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one plain CPU device, like the driver's box
    return subprocess.run(
        argv, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_without_a_tpu_fails_at_once_in_the_contract_shape():
    proc = _run([sys.executable, "chip_smoke.py"], timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.splitlines()
    parsed = _assert_contract_shape(lines[-1])
    assert parsed["ok"] is False
    assert parsed["device"]["platform"] == "cpu"
    assert proc.stdout.endswith(lines[-1] + "\n")
    # it started nothing: no server came up, no phase ran
    assert "nothing was started" in proc.stdout
    assert "server up" not in proc.stdout and "phase:" not in proc.stdout


def test_served_phase_rehearsal_on_cpu_ends_with_the_line_and_nothing_after():
    """The whole served phase at a tiny size, steered from here (an XLA
    device codec on the CPU backend, 12 MiB of needles): server child,
    shell children, ec.encode, degraded reads, two ec.rebuilds, every byte
    compared.  It must pass its checks, and STILL say ok:false — only a TPU
    earns ok:true — with nothing on stdout after the final line."""
    code = ("import chip_smoke; chip_smoke.run(codec='tpu_xor', "
            "payload_bytes=12 << 20, floor_bytes=4 << 20, gate=False, "
            "phases=('served',))")
    proc = _run([sys.executable, "-c", code], timeout=400)
    lines = proc.stdout.splitlines()
    assert "served phase: PASS" in proc.stdout, proc.stdout[-3000:]
    assert "all 14 shard files byte-identical" in proc.stdout
    assert "no jax import" in proc.stdout  # the shell stayed off the device
    parsed = _assert_contract_shape(lines[-1])
    assert parsed == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert proc.returncode == 1
    assert proc.stdout.endswith(lines[-1] + "\n")
    assert sum(ln.startswith('{"ok"') for ln in lines) == 1
    # children were redirected, not inherited: every earlier line is the
    # script's own, and the server's rpc log line never reached our streams
    assert all(ln.startswith("[chip_smoke +") for ln in lines[:-1])
    assert "dispatch=" not in proc.stdout + proc.stderr
