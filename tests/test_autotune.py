"""Autotuner tests (DESIGN.md §5): decision quality, disk-cache hit
path, stale invalidation, and the measured-row overlay."""

import json

import pytest

from repro.autotune import (
    candidate_kinds,
    choose_kind,
    should_split_pieces,
)
from repro.autotune import tuner as T
from repro.core.schedule import registered_kinds, resolve_kind


@pytest.fixture()
def env(tmp_path, monkeypatch):
    """Hermetic tuner env: private cache + bench artifact paths."""
    cache = tmp_path / "autotune.json"
    bench = tmp_path / "BENCH_maps.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_BENCH_ARTIFACT", str(bench))
    monkeypatch.delenv("REPRO_AUTOTUNE_DISABLE", raising=False)
    return {"cache": cache, "bench": bench}


def _bench_artifact(rows):
    return {"schema": "bench-maps/v2", "rows": rows}


def _row(m, n_elems, kind, us, steps, compiled=True, backend="cpu"):
    return {
        "test": f"ACCUM{m}D" if m > 2 else "ACCUM", "map": kind, "m": m,
        "n": n_elems, "grid_steps": steps, "waste": 0.0,
        "us_per_call": us, "backend": backend, "jax_version": "x",
        "compiled": compiled,
    }


def test_decision_is_concrete_and_cached(env):
    d = choose_kind(3, 8, backend="cpu")
    assert d.kind in registered_kinds(3)
    assert d.source in ("model", "measured")
    assert d.scores_us  # per-candidate scores recorded
    data = json.loads(env["cache"].read_text())
    assert data["schema"] == T.CACHE_SCHEMA
    assert "m=3,n=8,backend=cpu" in data["entries"]

    d2 = choose_kind(3, 8, backend="cpu")
    assert d2.source == "cache"
    assert d2.kind == d.kind


def test_cache_hit_does_not_recompute(env, monkeypatch):
    d = choose_kind(2, 16, backend="cpu")

    def boom(*a, **k):
        raise AssertionError("scored on a cache hit")

    monkeypatch.setattr(T, "_model_scores", boom)
    monkeypatch.setattr(T, "_measured_scores", boom)
    d2 = choose_kind(2, 16, backend="cpu")
    assert d2.source == "cache" and d2.kind == d.kind


def test_refresh_bypasses_cache(env, monkeypatch):
    choose_kind(2, 16, backend="cpu")
    d = choose_kind(2, 16, backend="cpu", refresh=True)
    assert d.source != "cache"


def test_stale_on_bench_artifact_change(env):
    choose_kind(3, 8, backend="cpu")
    env["bench"].write_text(json.dumps(_bench_artifact([])))
    d = choose_kind(3, 8, backend="cpu")
    assert d.source != "cache"  # fingerprint changed -> recompute
    assert choose_kind(3, 8, backend="cpu").source == "cache"


def test_stale_on_jax_version_change(env, monkeypatch):
    choose_kind(3, 8, backend="cpu")
    monkeypatch.setattr(T, "_jax_version", lambda: "999.0.0")
    d = choose_kind(3, 8, backend="cpu")
    assert d.source != "cache"


def test_measured_rows_win(env):
    """Measured ranking kicks in when every candidate has a row."""
    from repro.core.schedule import SimplexSchedule

    kinds = candidate_kinds(3, 8)
    assert "bb" in kinds
    rows = [
        _row(3, 32, k, us=(0.001 if k == "bb" else 1000.0),
             steps=SimplexSchedule(3, 8, k).steps)
        for k in kinds
    ]
    env["bench"].write_text(json.dumps(_bench_artifact(rows)))
    d = choose_kind(3, 8, backend="cpu")
    assert d.kind == "bb"
    assert d.source == "measured"


def test_partial_measured_coverage_keeps_model_ranking(env):
    """One measured row must not distort the ranking: mixing a
    whole-executor wall-clock with model overhead estimates would
    penalize exactly the kind that got benchmarked."""
    env["bench"].write_text(json.dumps(_bench_artifact([
        _row(3, 32, "bb", us=0.001, steps=8**3),
    ])))
    d = choose_kind(3, 8, backend="cpu")
    assert d.source == "model"


def test_interpret_rows_are_ignored(env):
    env["bench"].write_text(json.dumps(_bench_artifact([
        _row(3, 32, "bb", us=0.001, steps=8**3, compiled=False),
    ])))
    d = choose_kind(3, 8, backend="cpu")
    assert d.source == "model"  # emulator timing must not override


def test_other_backend_rows_are_ignored(env):
    env["bench"].write_text(json.dumps(_bench_artifact([
        _row(3, 32, "bb", us=0.001, steps=8**3, backend="tpu"),
    ])))
    d = choose_kind(3, 8, backend="cpu")
    assert d.source == "model"


def test_candidate_kinds_m2_excludes_linear_grid_kinds():
    for n in (8, 16, 12):
        ks = candidate_kinds(2, n)
        assert ks
        assert "table" not in ks and "composite" not in ks


def test_resolve_kind_auto_is_concrete(env):
    for m, n in [(2, 16), (2, 12), (3, 8), (3, 6), (4, 4)]:
        kind = resolve_kind(m, n, "auto", backend="cpu")
        assert kind != "auto"
        assert kind in registered_kinds(m)


def test_disable_env_skips_cache(env, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    d = choose_kind(3, 8, backend="cpu")
    assert d.source != "cache"
    assert not env["cache"].exists()


def test_should_split_pieces_threshold(monkeypatch):
    monkeypatch.delenv("REPRO_SPLIT_PIECES", raising=False)
    assert not should_split_pieces(2, 10**7)  # too few pieces
    assert not should_split_pieces(10, 100)  # chain cheaper than launches
    assert should_split_pieces(10, 10**7)


# ---------------------------------------------------------------------------
# Attention-impl decisions (ISSUE 9, DESIGN.md §8)
# ---------------------------------------------------------------------------


def _attn_row(kind, us, steps, compiled=True, backend="tpu"):
    return {
        "test": "ATTN", "map": kind, "m": 2, "n": 32, "grid_steps": steps,
        "us_per_call": us, "backend": backend, "jax_version": "x",
        "compiled": compiled,
    }


def test_attn_decision_serve_shape_is_folded_flash(env, monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_STEP_CAP", raising=False)
    d = T.choose_attn_impl(64, 4, 16, backend="cpu")
    assert (d.impl, d.kind) == ("flash", "folded")
    assert d.block_q == 32 and 64 % d.block_q == 0
    assert d.source == "model" and set(d.scores_us) == {
        "folded", "bb", "chunked"
    }
    data = json.loads(env["cache"].read_text())
    assert f"attn,s=64,h=4,d=16,backend=cpu,{T._ATTN_TILE_RULE}" in data["entries"]
    assert T.choose_attn_impl(64, 4, 16, backend="cpu").source == "cache"


def test_attn_decision_compiled_backend_prefers_folded(env):
    d = T.choose_attn_impl(4096, 32, 128, backend="tpu")
    assert (d.impl, d.kind, d.block_q) == ("flash", "folded", 512)


@pytest.mark.parametrize("seq,head_dim,block", [
    (8192, 128, 512),  # yi6b.prefill_8k: nq 16
    (1024, 128, 512),  # yi6b.prefill_1k: nq 2, the fold still has a pair
    (1920, 128, 128),  # 15 x 128: no larger tile divides it
    (8192, 64, 0),  # lane-misaligned head dim: no flash tile
])
def test_attn_compiled_tile_rule(env, seq, head_dim, block):
    assert T.attn_block_q(seq, head_dim, backend="tpu") == block
    d = T.choose_attn_impl(seq, 32, head_dim, backend="tpu")
    assert d.block_q == block
    if block:  # the ranking stays on the folded kernel at the new tile
        assert (d.impl, d.kind, d.source) == ("flash", "folded", "model")
    else:
        assert (d.impl, d.source) == ("chunked", "fallback")


def test_attn_entry_of_an_older_tile_rule_is_recomputed(env):
    # a decision cached under the 128 rule carries the same JAX version
    # and bench fingerprint; serving its tile would hide the new rule's.
    old_key = "attn,s=8192,h=32,d=128,backend=tpu"
    entry = {
        "impl": "flash", "kind": "folded", "block_q": 128, "source": "model",
        "score_us": 1.0, "scores_us": {}, "jax_version": T._jax_version(),
        "fingerprint": T._fingerprint(str(env["bench"])),
    }
    env["cache"].write_text(json.dumps(
        {"schema": T.CACHE_SCHEMA, "entries": {old_key: entry}}))
    d = T.choose_attn_impl(8192, 32, 128, backend="tpu")
    assert d.source == "model" and d.block_q == 512
    again = T.choose_attn_impl(8192, 32, 128, backend="tpu")
    assert again.source == "cache" and again.block_q == 512
    # the old rule's entry stays for code that keeps the old rule
    entries = json.loads(env["cache"].read_text())["entries"]
    assert entries[old_key]["block_q"] == 128


def test_attn_interpret_step_cap_falls_back(env, monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_STEP_CAP", raising=False)
    d = T.choose_attn_impl(4096, 32, 128, backend="cpu")
    assert (d.impl, d.source) == ("chunked", "fallback")
    monkeypatch.setenv("REPRO_ATTN_STEP_CAP", "10000000")
    d2 = T.choose_attn_impl(4096, 32, 128, backend="cpu", refresh=True)
    assert d2.impl == "flash"


def test_attn_unmappable_seq_falls_back(env):
    d = T.choose_attn_impl(100, 4, 16, backend="cpu")  # no tile divides 100
    assert (d.impl, d.kind, d.block_q) == ("chunked", "chunked", 0)
    assert d.source == "fallback"


def test_attn_compiled_lane_alignment_falls_back(env):
    # head_dim 64 misses the 8x128 Mosaic lane contract on compiled
    # backends -> chunked (interpret hosts may still map it).
    d = T.choose_attn_impl(4096, 8, 64, backend="tpu")
    assert (d.impl, d.source) == ("chunked", "fallback")


def test_attn_measured_rows_win(env):
    from repro.kernels.flash_attention import flash_grid_steps

    heads = 32
    steps_f = heads * flash_grid_steps(32, "folded")
    steps_b = heads * flash_grid_steps(32, "bb")
    env["bench"].write_text(json.dumps(_bench_artifact([
        _attn_row("folded", 500.0, steps_f),
        _attn_row("bb", 600.0, steps_b),
        _attn_row("chunked", 10.0, steps_f),
    ])))
    d = T.choose_attn_impl(4096, heads, 128, backend="tpu")
    assert (d.impl, d.kind, d.source) == ("chunked", "chunked", "measured")


def test_attn_partial_measured_coverage_keeps_model(env):
    env["bench"].write_text(json.dumps(_bench_artifact([
        _attn_row("chunked", 10.0, 1000),
    ])))
    d = T.choose_attn_impl(4096, 32, 128, backend="tpu")
    assert d.source == "model" and d.kind == "folded"


def test_attn_interpret_measured_rows_are_ignored(env):
    rows = [_attn_row(k, 1.0, 1000, compiled=False)
            for k in ("folded", "bb", "chunked")]
    env["bench"].write_text(json.dumps(_bench_artifact(rows)))
    d = T.choose_attn_impl(4096, 32, 128, backend="tpu")
    assert d.source == "model"


def test_attn_block_q_shapes():
    assert T.attn_block_q(64, 16, backend="cpu") == 32  # nq>=2 preferred
    assert T.attn_block_q(4096, 128, backend="tpu") == 512
    assert T.attn_block_q(384, 128, backend="tpu") == 128  # 3 x 128
    assert T.attn_block_q(4096, 16, backend="cpu") == 128  # interpret cap
    assert T.attn_block_q(100, 16, backend="cpu") == 0  # nothing divides
    assert T.attn_block_q(4096, 64, backend="tpu") == 0  # lane misaligned
