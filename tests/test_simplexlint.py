"""Pytest bridge for the simplexlint registry (DESIGN.md §9).

Three layers:
  * the tier-1 bridge — the full registry runs clean on the real tree
    (same invocation as ``scripts/simplexlint.py`` / CI);
  * AST fixture tests — each policy pass flags exactly its seeded
    violation under ``tests/fixtures_lint/bad`` and accepts the clean
    fixture module;
  * semantic violator tests — corrupted schedule views and
    mis-declared kernel bodies built in code, so the write-race,
    bijectivity, and halo-conformance checkers each catch a seeded
    violation without touching the real registry.
"""

import json
import pathlib
import shutil

import numpy as np
import pytest

from repro.analysis import (
    findings_to_json,
    get_pass,
    registered_passes,
    run_passes,
)
from repro.analysis.halo_passes import HALO_MN, check_body_halo
from repro.analysis.schedule_passes import (
    DEFAULT_MN,
    check_schedule_bijectivity,
    check_schedule_race,
    verified_schedules,
)
from repro.core.schedule import SimplexSchedule

REPO = pathlib.Path(__file__).resolve().parents[1]
BAD = REPO / "tests" / "fixtures_lint" / "bad"
CLEAN = REPO / "tests" / "fixtures_lint" / "clean"

AST_PASSES = ("design-xref", "hardcoded-interpret", "pallas-front-door",
              "shim-deprecation", "tile-alignment")


# --------------------------------------------------------------------------
# tier-1 bridge: the registry is clean on the merged tree
# --------------------------------------------------------------------------

def test_registry_clean_on_repo():
    findings = run_passes(REPO)
    assert not findings, "\n".join(f.format() for f in findings)


def test_registry_contents():
    names = registered_passes()
    for expected in AST_PASSES + (
        "schedule-bijectivity", "write-race", "halo-conformance",
    ):
        assert expected in names
    assert get_pass("hardcoded-interpret").fix is not None
    with pytest.raises(ValueError):
        get_pass("no-such-pass")


# --------------------------------------------------------------------------
# AST passes against the seeded fixtures
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pass_name,fixture,needle", [
    ("pallas-front-door", "rogue_pallas.py", "front"),
    ("hardcoded-interpret", "hard_interp.py", "interpret=True"),
    ("shim-deprecation", "shim_silent.py", "DeprecationWarning"),
    ("design-xref", "stale_xref.py", "stale cross-reference"),
    ("tile-alignment", "bad_tile.py", "sublane"),
])
def test_ast_pass_flags_exactly_its_fixture(pass_name, fixture, needle):
    findings = run_passes(REPO, src_root=BAD, passes=[pass_name])
    assert findings, f"{pass_name} missed its seeded violation"
    assert all(f.pass_name == pass_name for f in findings)
    # exactly the intended fixture file is flagged, nothing else
    assert {pathlib.Path(f.path).name for f in findings} == {fixture}
    assert any(needle in f.message for f in findings)


def test_shim_pass_flags_all_three_contract_breaks():
    msgs = [
        f.message
        for f in run_passes(REPO, src_root=BAD, passes=["shim-deprecation"])
    ]
    assert any("silent_shim" in m for m in msgs)  # delegates, no warning
    assert any("warning_reimplementor" in m for m in msgs)  # no delegation
    assert any("SilentShimClass" in m for m in msgs)  # class, no warning


def test_clean_fixture_passes_every_ast_pass():
    findings = run_passes(REPO, src_root=CLEAN, passes=list(AST_PASSES))
    assert not findings, "\n".join(f.format() for f in findings)


def test_fixer_rewrites_hardcoded_interpret(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    shutil.copy(BAD / "hard_interp.py", src / "hard_interp.py")
    before = run_passes(tmp_path, src_root=src,
                        passes=["hardcoded-interpret"])
    assert before and before[0].fixable
    after = run_passes(tmp_path, src_root=src,
                       passes=["hardcoded-interpret"], fix=True)
    assert not after
    fixed = (src / "hard_interp.py").read_text()
    assert 'engine.accum(x, rho=2, kind="bb", interpret=None)' in fixed


def test_json_report_schema():
    findings = run_passes(REPO, src_root=BAD, passes=["tile-alignment"])
    doc = json.loads(findings_to_json(findings, ["tile-alignment"]))
    assert doc["version"] == 1
    assert doc["passes"] == ["tile-alignment"]
    assert doc["counts"] == {"tile-alignment": len(findings)}
    assert len(doc["findings"]) == len(findings) > 0
    assert set(doc["findings"][0]) == {
        "pass", "path", "line", "message", "fixable",
    }


def test_cli_exit_codes(capsys):
    from repro.analysis.cli import main

    assert main(["--root", str(REPO)]) == 0
    capsys.readouterr()
    assert main(["--root", str(REPO), "--list"]) == 0
    listed = capsys.readouterr().out
    for name in registered_passes():
        assert name in listed


# --------------------------------------------------------------------------
# semantic violators built in code
# --------------------------------------------------------------------------

class _Corrupted:
    """Schedule view whose evaluated walk is mutated post hoc, so each
    semantic checker can be fed exactly one seeded violation."""

    def __init__(self, base, mutate):
        self._base = base
        self._mutate = mutate
        self.m, self.n = base.m, base.n
        self.kind = f"corrupted-{base.kind}"
        self.grid, self.steps = base.grid, base.steps
        self.prefetch = getattr(base, "prefetch", None)

    def map(self, *ws):
        out = self._base.map(*ws)
        coords = [np.asarray(c).astype(np.int64).copy() for c in out[:-1]]
        valid = np.asarray(out[-1]).astype(bool).copy()
        self._mutate(coords, valid)
        return tuple(coords) + (valid,)


def _first_valid_pair(base):
    from repro.analysis.schedule_passes import eval_schedule_map

    _, valid = eval_schedule_map(base)
    idx = np.nonzero(valid)[0]
    return int(idx[0]), int(idx[1])


def test_write_race_catches_duplicate_output_block():
    base = SimplexSchedule(2, 4, "bb")
    i, j = _first_valid_pair(base)

    def mutate(coords, valid):
        for c in coords:
            c[j] = c[i]

    findings = check_schedule_race(_Corrupted(base, mutate), 2, 4)
    assert findings
    assert all("write race" in f.message for f in findings)
    assert not check_schedule_race(base, 2, 4)


def test_write_race_catches_invalid_step_without_trash_row():
    base = SimplexSchedule(2, 4, "hmap")
    i, _ = _first_valid_pair(base)

    def mutate(coords, valid):
        valid[i] = False

    parked = _Corrupted(base, mutate)  # states no exactness: a trash row
    assert not check_schedule_race(parked, 2, 4)
    claimed = _Corrupted(base, mutate)
    claimed.exact = True  # launched with no trash row to park on
    findings = check_schedule_race(claimed, 2, 4)
    assert findings
    assert all("without a trash row" in f.message for f in findings)
    assert not check_schedule_race(base, 2, 4)


def test_bijectivity_catches_coverage_hole():
    base = SimplexSchedule(2, 4, "bb")
    i, _ = _first_valid_pair(base)

    def mutate(coords, valid):
        valid[i] = False

    findings = check_schedule_bijectivity(_Corrupted(base, mutate), 2, 4)
    assert any("never visited" in f.message for f in findings)
    assert not check_schedule_bijectivity(base, 2, 4)


def test_bijectivity_catches_out_of_bounds():
    base = SimplexSchedule(2, 4, "bb")
    i, _ = _first_valid_pair(base)

    def mutate(coords, valid):
        coords[0][i] = 99

    findings = check_schedule_bijectivity(_Corrupted(base, mutate), 2, 4)
    assert any("out-of-bounds" in f.message for f in findings)


def test_halo_pass_catches_undeclared_read():
    from repro.kernels.engine import CABody

    class UnderDeclared(CABody):
        name = "lint-test-under-declared"

        def stencil(self, m):
            return ((0,) * m,)  # claims centre-only while halo=True

    findings = check_body_halo(UnderDeclared(), 2, 4, "bb")
    assert findings
    assert all("undeclared halo read" in f.message for f in findings)
    assert len(findings) == 3 ** 2 - 1  # every non-centre offset


def test_halo_pass_catches_stale_declaration():
    from repro.kernels.engine import AccumBody, halo_shifts

    class OverDeclared(AccumBody):
        name = "lint-test-over-declared"

        def stencil(self, m):
            return halo_shifts(m)  # claims a halo the engine never fetches

    findings = check_body_halo(OverDeclared(), 2, 4, "bb")
    assert findings
    assert all("stale stencil" in f.message for f in findings)


def test_halo_pass_clean_on_registered_bodies():
    from repro.analysis.halo_passes import _domain_bodies

    bodies = list(_domain_bodies())
    assert bodies
    for body in bodies:
        for m, nb, kind in HALO_MN:
            assert not check_body_halo(body, m, nb, kind)


def test_verified_matrix_covers_kinds_and_shards():
    from repro.core.schedule import registered_kinds, resolve_kind

    assert set(DEFAULT_MN) == {2, 3, 4}
    for m, ns in DEFAULT_MN.items():
        assert any(n & (n - 1) == 0 for n in ns)  # a pow2 side
        assert any(n & (n - 1) != 0 for n in ns)  # a non-pow2 side
        for n in ns:
            labels = [label for label, _ in verified_schedules(m, n)]
            assert any(lbl.startswith("shard(k=") for lbl in labels)
            resolved = {resolve_kind(m, n, k) for k in registered_kinds(m)}
            covered = {
                lbl.split("->")[-1] for lbl in labels
                if not lbl.startswith(("shard(", "composite-pieces"))
            }
            assert covered == resolved
