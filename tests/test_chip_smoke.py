"""chip_smoke.py's parent logic, as far as a CPU can take it: the parent
never imports JAX, a run without a chip says ``"ok": false`` and exits
non-zero, and so does any phase that fails."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, env=None, timeout=300):
    out = subprocess.run([sys.executable, *argv], cwd=cwd, timeout=timeout,
                         env=env or dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return out, lines


def test_parent_imports_neither_jax_nor_the_package():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'llama_fastapi_k8s_gpu_tpu', 'numpy')]; "
            "assert not bad, bad" % REPO)
    out, _ = _run(["-c", code])
    assert out.returncode == 0, out.stderr[-2000:]


def test_no_chip_is_a_failure_not_a_cpu_pass(tmp_path):
    """With JAX held to the CPU the device phase fails, the last line says
    ok false, the exit code is not 0, and no model file was written."""
    work = shutil.copytree(
        REPO, tmp_path / "co", ignore=shutil.ignore_patterns(
            ".git", ".chip_smoke", ".lfkt_xla_cache", "chiprun_out",
            "__pycache__", "tests", "docs", "*.gguf"))
    out, lines = _run([os.path.join(work, "chip_smoke.py")], cwd=work)
    assert out.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["phase"] == "device", last
    assert not any('"ok": true' in ln for ln in lines), lines
    assert not [f for f in os.listdir(os.path.join(work, ".chip_smoke"))
                if f.endswith(".gguf")]


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out, lines = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert out.returncode != 0
    assert json.loads(lines[-1])["ok"] is False


@pytest.mark.parametrize("last_line,rc", [
    ('{"phase": "kernels", "ok": false, "error": "outside its tolerance", "final": true}', 0),
    ('{"phase": "kernels", "ok": true, "final": true}', 3),
    ('{"phase": "kernels", "ok": true}', 0),
    ('not json', 0),
], ids=["says-not-ok", "exits-nonzero", "no-final-line", "no-json"])
def test_a_failing_phase_fails_the_run(tmp_path, monkeypatch, capsys,
                                       last_line, rc):
    """run_child trusts a phase only when the child exited 0 AND its last
    JSON line is marked final AND says ok; the parent then prints
    ``"ok": false`` with the phase and returns non-zero."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    fake = tmp_path / "fake_child.py"
    fake.write_text("import sys\nprint(%r)\nsys.exit(%d)\n" % (last_line, rc))
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "__file__", str(fake))
    monkeypatch.setattr(
        chip_smoke, "run_one_chip",
        lambda args, deadline: chip_smoke.run_child("kernels", [], 60))
    monkeypatch.setattr(chip_smoke.shutil, "rmtree", lambda *a, **k: None)
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and last["phase"] == "kernels", last
