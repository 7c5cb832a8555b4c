"""chip_smoke.py's phases at tiny sizes in interpret mode on the CPU.

The chip run uses the same functions at full size; here they check the
control flow and the oracles, and ``main()`` must refuse the CPU.
"""

import importlib.util
import json
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _hermetic(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    # with the variable set, enable_compile_cache() leaves JAX as it is
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_attention_phase_tiny(smoke):
    line = smoke.phase_attention(b=1, hq=4, hkv=1, s=64, d=16)
    assert "decision flash/folded" in line


@pytest.mark.parametrize("body,m,n", [
    ("accum", 2, 32), ("edm", 2, 32), ("ca", 2, 32), ("accum", 3, 16),
])
def test_engine_phase_tiny(smoke, body, m, n):
    line = smoke.phase_engine(body, m, n)
    assert "hmap == bb bitwise" in line


def test_engine_phase_catches_a_wrong_oracle(smoke, monkeypatch):
    from repro.kernels import ref

    monkeypatch.setattr(ref, "accum_md", lambda x: x + 2)
    with pytest.raises(AssertionError, match="elements differ"):
        smoke.phase_engine("accum", 2, 32)


def test_serve_phase_smoke_config(smoke):
    line = smoke.phase_serve("yi-6b", batch=2, prompt_len=64, gen=2,
                             smoke=True)
    assert "attention flash/folded" in line and "logits finite" in line


def test_sharded_ca_phase_one_device(smoke):
    line = smoke.phase_sharded_ca(m=2, n=32, k=1, gens=2)
    assert "spmd bit-exact" in line and "engine bit-exact" in line


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert '"ok"' not in out.out


@pytest.mark.parametrize("platform,interpret,want", [
    ("tpu", False, None),
    ("cpu", True, "needs a TPU"),
    ("tpu", True, "interpret"),
])
def test_preflight(smoke, platform, interpret, want):
    got = smoke.preflight(platform, interpret)
    assert (got is None) if want is None else (want in got)


def test_result_line_shape(smoke, monkeypatch, capsys):
    # with every phase stubbed out, main's last line is the contract's
    monkeypatch.setattr(smoke, "preflight", lambda platform, interp: None)
    monkeypatch.setattr(smoke, "_phases", lambda chips: [
        ("stub", lambda: "fine")])
    monkeypatch.setattr(
        "repro.compile_cache.enable_compile_cache", lambda: None
    )
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(last)
    assert res["ok"] is True
    assert set(res["device"]) == {"platform", "kind", "count"}


def test_failing_phase_prints_no_result(smoke, monkeypatch, capsys):
    def boom():
        raise AssertionError("broken")

    monkeypatch.setattr(smoke, "preflight", lambda platform, interp: None)
    monkeypatch.setattr(smoke, "_phases", lambda chips: [("boom", boom)])
    monkeypatch.setattr(
        "repro.compile_cache.enable_compile_cache", lambda: None
    )
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAIL boom" in out and '"ok"' not in out
