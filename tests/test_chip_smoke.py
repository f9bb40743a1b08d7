"""chip_smoke.py off the chip: it refuses to run without a TPU or outside a
checkout, and its one-chip phase runs end to end at a tiny size on CPU (the
Pallas kernels interpreted) with every check it makes on the chip."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), '..')
sys.path.insert(0, REPO)

import chip_smoke as CS  # noqa: E402

TINY = CS.Sizes(slots=4, max_seq=128, chunk=16, page=8, prefix=32,
                prompt_lens=(40, 90), requests=4, new_tokens=3,
                score_lens=(18, 50))


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _run(os.path.join(REPO, 'chip_smoke.py'), REPO)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, 'chip_smoke.py'), tmp_path)
    out = _run(str(tmp_path / 'chip_smoke.py'), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_worst_rel_err():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((5, 4000)).astype(np.float32)
    b = rng.standard_normal((5, 4000)).astype(np.float32)
    assert CS.worst_rel_err([a], [a]) == 0.0
    assert abs(CS.worst_rel_err([a], [b]) - np.sqrt(2)) < 0.1
    bad = a.copy()
    bad[3] = b[3]                       # one wrong position is enough
    assert CS.worst_rel_err([bad], [a]) > CS.LOGIT_TOL


def test_single_chip_phase_tiny(monkeypatch, capsys):
    """Serve (prefix hits, ring wraparound past the window) and the three
    scoring comparisons, as on the chip but at the smoke config's size."""
    from repro.launch.serve import load_model
    monkeypatch.setattr(CS, 'check_compiled_kernels', lambda: None)
    model, params = load_model('gemma3-1b', published=False, seed=0)
    assert TINY.prompt_lens[0] > model.cfg.window
    CS.single_chip(model, params, model.build_table(params), TINY, seed=0)
    out = capsys.readouterr().out
    assert 'prefix hits' in out and 'precompute on vs off' in out


def test_fast_path_check_catches_dropped_fusion(monkeypatch):
    """An engine that silently turned fused gather->RoPE off fails."""
    from repro.launch.serve import build_engine, load_model
    monkeypatch.setattr(CS, 'check_compiled_kernels', lambda: None)
    model, params = load_model('gemma3-1b', published=False, seed=0)
    eng = build_engine(model, params, None, **CS.engine_options(TINY))
    with pytest.raises(CS.SmokeFailure, match='gather->RoPE'):
        CS.check_fast_path(eng)
