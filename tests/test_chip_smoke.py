"""chip_smoke.py and paddle_tpu.chip, as far as a machine without a chip
can check them: the two phase functions pass at LlamaConfig.tiny() with
the platform check lifted, the script fails — loudly — where jax finds
no TPU or a phase fails, and the compile cache goes where the contract
says."""
import os
import subprocess
import sys

import jax
import pytest

from paddle_tpu import chip
from paddle_tpu.models import LlamaConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


@pytest.fixture
def cache_config_restored():
    """enable_compile_cache writes process-wide state; put it back."""
    env = os.environ.get(chip.CACHE_ENV)
    cfg = jax.config.jax_compilation_cache_dir
    yield
    if env is None:
        os.environ.pop(chip.CACHE_ENV, None)
    else:
        os.environ[chip.CACHE_ENV] = env
    jax.config.update("jax_compilation_cache_dir", cfg)


def test_cache_env_set_means_code_sets_nothing(monkeypatch,
                                               cache_config_restored):
    monkeypatch.setenv(chip.CACHE_ENV, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert chip.enable_compile_cache() is None
    assert os.environ[chip.CACHE_ENV] == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_checkout(monkeypatch, cache_config_restored):
    monkeypatch.delenv(chip.CACHE_ENV, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert chip.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ[chip.CACHE_ENV] == want     # children inherit it


def test_require_tpu_fails_on_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        chip.require_tpu()


def test_chip_env_binds_one_chip():
    assert chip.chip_env(2) == {"TPU_VISIBLE_CHIPS": "2",
                                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                                "TPU_PROCESS_BOUNDS": "1,1,1"}


def test_train_phase_tiny(cache_config_restored):
    rec = chip_smoke.phase_train(cfg=LlamaConfig.tiny(), batch=2, seq=32,
                                 require_chip=False)
    assert len(rec["losses"]) == 5 and rec["losses"][-1] < rec["losses"][0]
    assert rec["device"]["platform"] == "cpu"


def test_serve_phase_tiny(cache_config_restored):
    rec = chip_smoke.phase_serve(
        cfg=LlamaConfig.tiny(num_hidden_layers=1), max_len=32, page_size=16,
        max_batch=2, prompt_lens=(5, 17), new_tokens=3, require_chip=False)
    run, = rec["runs"]
    assert (run["done"], run["failed"]) == (2, 0)
    agree = rec["agreement"]["tp1"]
    assert agree["identical_requests"] == 2 and agree["splits"] == 0


def test_probe_scores_a_wrong_token_as_a_split(cache_config_restored):
    """The correctness check can fail: hand the reference engine a
    candidate stream with one wrong token and it must score a margin
    past TIE_TOL at exactly that position."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    kw = dict(max_len=32, page_size=16, max_batch=2)
    prompt = np.arange(5, dtype=np.int64)
    eng = ContinuousBatchingEngine(model, **kw)
    good, _ = chip_smoke._serve_stream(eng, [prompt], 3)
    bad = {0: [good[0][0], (good[0][1] + 1) % 256, good[0][2]]}
    Probe = chip_smoke._make_probe_engine(
        ContinuousBatchingEngine, {"good": good, "bad": bad})
    chip_smoke._serve_stream(Probe(model, **kw), [prompt], 3)
    assert Probe.first_split["good"] == {}
    pos, margin = Probe.first_split["bad"][0]
    assert pos == 1 and margin > chip_smoke.TIE_TOL


def _run_smoke(*argv, **env):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_no_tpu_is_a_failure_and_says_why():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_failing_phase_fails_the_parent(monkeypatch):
    """Any child exiting non-zero — here an unknown phase — makes the
    parent exit non-zero without a result line."""
    rc, res = chip_smoke._run_phase(["--phase", "nonesuch"], timeout=60)
    assert rc != 0 and res is None
    monkeypatch.setattr(chip_smoke, "PHASES_1",
                        [("broken", ["--phase", "nonesuch"], 60)])
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code == 1
