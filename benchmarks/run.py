"""Benchmark harness aggregator — one section per paper table/figure.

  Fig. 10  -> bench_maps_2simplex   (2-simplex: MAP/ACCUM/EDM/CA2D)
  Fig. 13  -> bench_maps_3simplex   (3-simplex: MAP3D/ACCUM3D/CA3D)
  Fig12/15 -> bench_energy          (EPS/W, modeled — DESIGN.md §2)
  §6/Thm6.2-> bench_general_m       ((r, beta) optimization table)
  beyond   -> bench_attention       (folded-simplex causal attention)

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the
full per-table CSVs, and writes ``BENCH_maps.json`` — the per-(kind, m,
n) steps/waste/wall-time artifact future PRs diff their perf trajectory
against.  Roofline tables come from the dry-run artifacts (see
EXPERIMENTS.md §Roofline), not from this harness.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time


def _map_rows_md(m: int = 4, n: int = 16, rho: int = 2):
    """General-m section of the artifact: the m>=4 schedules plus a
    wall-clock of the accum_md kernel they drive (interpret mode)."""
    import jax
    import jax.numpy as jnp

    from repro.core.schedule import SimplexSchedule, registered_kinds
    from repro.kernels import engine as Eng

    nb = n // rho
    x = jax.random.randint(jax.random.PRNGKey(0), (n,) * m, 0, 50).astype(
        jnp.int32
    )
    rows = []
    bb_steps = SimplexSchedule(m, nb, "bb").steps
    reps = 3
    for kind in registered_kinds(m):
        sched = SimplexSchedule(m, nb, kind)
        f = jax.jit(lambda kind=kind: Eng.accum_md(x, rho=rho, kind=kind))
        jax.block_until_ready(f())  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(f())
        us = (time.perf_counter() - t0) / reps * 1e6
        rows.append({
            "test": f"ACCUM{m}D", "map": kind, "m": m, "n": n,
            "grid_steps": sched.steps, "waste": sched.waste(),
            "space_speedup_vs_bb": bb_steps / sched.steps,
            "us_per_call": us,
        })
    return rows


def _composite_rows():
    """Composite-vs-table at non-pow2 n, m in {2, 3, 4} (DESIGN.md §4.2).

    Two facts per (m, n): the parallel-space cost (grid_steps/waste — the
    composite pays a bounded analytical premium, the table walk is exact)
    and the HOST-side schedule-construction wall time (us_per_call) — the
    table kind pays the O(V) enumeration, the composite O(pieces).  The
    n ladder quadruples per m so the artifact shows the table build time
    scaling ~V while the composite stays flat.
    """
    from repro.core.schedule import SimplexSchedule

    ladders = {2: [24, 96, 384, 1536], 3: [24, 96, 192], 4: [24, 48]}
    rows = []
    for m, ns in ladders.items():
        for n in ns:
            for kind in ("composite", "table"):
                t0 = time.perf_counter()
                sched = SimplexSchedule(m, n, kind)
                sched.prefetch  # force the table build (lazy; None for composite)
                build_us = (time.perf_counter() - t0) * 1e6
                rows.append({
                    "test": f"SCHED_BUILD{m}D", "map": kind, "m": m, "n": n,
                    "grid_steps": sched.steps, "waste": sched.waste(),
                    "us_per_call": build_us,
                })
    return rows


def _compiled_rows(quick: bool = False):
    """Compiled-execution section: ``compiled: true`` rows for ACCUM
    (m=2, n=256) and ACCUM3D.  The launched kind is autotuner-selected
    (``repro.autotune.choose_kind`` — the harness never hand-picks a
    schedule, the winner row carries ``autotune_source``), and every
    *candidate* kind is additionally timed and recorded so the tuner's
    measured ranking has symmetric evidence on the next run (it only
    trusts measurements that cover all candidates).  On this host
    "compiled" means the fused-XLA executors of ``kernels/compiled.py``
    (one jit program for the whole schedule walk); on TPU/GPU the same
    entry points lower as non-interpret Pallas.  Each output is
    parity-checked against the pure-numpy truth before its row is
    recorded — a wrong compiled walk aborts the run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.autotune import candidate_kinds, choose_kind
    from repro.core.schedule import SimplexSchedule
    from repro.kernels.compiled import accum2d_compiled, accum3d_compiled
    from repro.kernels.policy import backend_name

    backend = backend_name()
    reps = 3 if quick else 10
    rows = []

    def _timed(f, *args):
        out = jax.block_until_ready(f(*args))  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(f(*args))
        return out, (time.perf_counter() - t0) / reps * 1e6

    def _section(test, m, n, rho, runner, x, want):
        decision = choose_kind(m, n // rho, backend=backend)
        for kind in candidate_kinds(m, n // rho):
            out, us = _timed(runner, x, rho, kind)
            if not np.array_equal(np.asarray(out), want):
                raise SystemExit(f"compiled {test} parity FAILED ({kind})")
            sched = SimplexSchedule(m, n // rho, kind)
            row = {
                "test": test, "map": kind, "m": m, "n": n, "rho": rho,
                "grid_steps": sched.steps, "waste": sched.waste(),
                "us_per_call": us, "compiled": True,
            }
            if kind == decision.kind:
                row["autotune_source"] = decision.source
            rows.append(row)

    # -- ACCUM, m=2, n=256 --------------------------------------------
    n2, rho2 = 256, 16
    x2 = jax.random.randint(jax.random.PRNGKey(0), (n2, n2), 0, 100)
    x2 = x2.astype(jnp.int32)
    want2 = np.asarray(x2) + np.tri(n2, dtype=np.int32)
    _section("ACCUM", 2, n2, rho2, accum2d_compiled, x2, want2)

    # -- ACCUM3D ------------------------------------------------------
    n3, rho3 = (32, 4) if quick else (64, 4)
    x3 = jax.random.randint(jax.random.PRNGKey(1), (n3,) * 3, 0, 50)
    x3 = x3.astype(jnp.int32)
    ii = np.arange(n3)
    simplex = (
        ii[:, None, None] + ii[None, :, None] + ii[None, None, :]
    ) < n3
    want3 = np.asarray(x3) + simplex.astype(np.int32)
    _section("ACCUM3D", 3, n3, rho3, accum3d_compiled, x3, want3)
    return rows


def _engine_parity_rows(quick: bool = False):
    """ENGINE_PARITY section: the differential harness as artifact rows.

    For each registered engine body x dimension x schedule kind, run the
    engine-built kernel and record ``max_abs_err`` against the strongest
    available baseline — the frozen hand-rolled kernel in
    ``kernels/legacy.py`` where one exists (bit-parity expected, so the
    recorded err must be 0), else the ``kernels/ref.py`` numpy oracle on
    the domain (float-tolerance for the m >= 3 EDM bodies).  A non-zero
    integer-body error aborts the run: a silently wrong engine must
    never produce a plausible-looking benchmark artifact.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import engine as Eng
    from repro.kernels import legacy as Leg
    from repro.kernels import ref as Ref

    kinds = {
        2: ["hmap", "bb"] if quick else ["hmap", "rb", "bb", "composite"],
        3: ["hmap", "table"] if quick else
           ["hmap", "octant", "bb", "table", "composite"],
        4: ["hmap", "composite"] if quick else
           ["hmap", "bb", "table", "composite"],
    }
    sizes = {2: (16, 4), 3: (8, 2), 4: (8, 2)}
    legacy_2d = ("hmap", "rb", "bb")
    rows = []
    for m, (n, rho) in sizes.items():
        msk = np.asarray(Ref.simplex_mask(m, n))
        x = jnp.asarray((np.arange(n**m, dtype=np.int64) % 97).astype(
            np.int32).reshape((n,) * m))
        p = jax.random.normal(jax.random.PRNGKey(m), (n, 3), jnp.float32)
        s = (jax.random.uniform(jax.random.PRNGKey(m + 8), (n,) * m)
             < 0.4).astype(jnp.int32) * Ref.simplex_mask(m, n, jnp.int32)

        def _cases(kind):
            has_legacy = m != 2 or kind in legacy_2d
            yield ("accum", Eng.accum(x, rho=rho, kind=kind),
                   ({2: Leg.accum2d, 3: Leg.accum3d}.get(m, Leg.accum_md)(
                       x, rho=rho, kind=kind) if has_legacy
                    else jnp.where(Ref.simplex_mask(m, n), Ref.accum_md(x),
                                   x)),
                   True)
            edm = (Eng.edm2d(p, rho=rho, kind=kind) if m == 2
                   else Eng.edm_md(p, m, rho=rho, kind=kind))
            edm_base = (Leg.edm2d(p, rho=rho, kind=kind)
                        if m == 2 and has_legacy else Ref.edm_md(p, m))
            yield ("edm", edm, edm_base, m == 2 and has_legacy)
            ca = Eng.ca(s, rho=rho, kind=kind)
            if m in (2, 3) and has_legacy:
                ca_base = {2: Leg.ca2d, 3: Leg.ca3d}[m](s, rho=rho, kind=kind)
                exact = True
            elif m == 2:
                # no legacy baseline for this kind; periodic 2-simplex oracle
                ca_base = jnp.where(Ref.simplex_mask(m, n),
                                    Ref.ca2d_step(s), s)
                exact = True
            else:
                ca_base = jnp.where(Ref.simplex_mask(m, n),
                                    Ref.ca_md_step(s), s)
                exact = True
            yield ("ca", ca, ca_base, exact)

        for kind in kinds[m]:
            sched_steps = Eng.grid_steps(n // rho, kind, m=m)
            for body, got, base, exact in _cases(kind):
                err = float(np.max(np.abs(
                    np.asarray(got, dtype=np.float64)
                    - np.asarray(base, dtype=np.float64)
                )))
                if exact and err != 0.0:
                    raise SystemExit(
                        f"ENGINE_PARITY FAILED: body={body} m={m} "
                        f"kind={kind} max_abs_err={err}"
                    )
                if not exact and err > 1e-4:
                    raise SystemExit(
                        f"ENGINE_PARITY FAILED (tolerance): body={body} "
                        f"m={m} kind={kind} max_abs_err={err}"
                    )
                rows.append({
                    "test": "ENGINE_PARITY", "body": body, "map": kind,
                    "m": m, "n": n, "grid_steps": sched_steps,
                    "max_abs_err": err,
                })
    return rows


def _shard_rows(quick: bool = False):
    """SHARD_SKEW section: fold-partition balance + sharded bit-exactness.

    For each (m, n, kind, shards) cell, record the fold partition's
    block-volume skew (max/mean shard steps — bounded by 1 + k/S, the
    information-theoretic optimum) next to the naive equal-thickness
    slab baseline (~m x), plus ``bit_exact`` for the cells where the
    sharded CA is actually executed against the single-device engine
    (DESIGN.md §7).  A sharded-CA mismatch aborts the run.  Runs the
    same under 1 or k devices — with >= ``shards`` devices the engine
    launches are placed round-robin on a real mesh
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in CI).
    """
    import jax
    import numpy as np

    from repro.core.schedule import SimplexSchedule, resolve_kind
    from repro.distributed.simplex_sharding import (
        shard_mesh, shard_skew, sharded_ca, slab_skew,
    )
    from repro.kernels import ref as Ref
    from repro.kernels.engine import default_rho
    from repro.kernels.ops import simplex_ca2d, simplex_ca_md

    # skew cells are analytic (O(1)); CA bit-exactness runs on the
    # moderate cells where interpret-mode Pallas stays fast.
    skew_ns = {2: [64, 128, 256] if quick else [64, 128, 192, 256],
               3: [16, 32, 64] if quick else [16, 32, 64, 128]}
    ca_cells = ({(2, 64), (3, 16), (3, 32)} if quick
                else {(2, 64), (3, 16), (3, 32)})
    rows = []
    for m, ns in skew_ns.items():
        rho = default_rho(m)
        for n in ns:
            nb = n // rho
            kind = resolve_kind(m, nb, "hmap" if m == 2 else "table")
            sched = SimplexSchedule(m, nb, kind)
            for k in (2, 4, 8):
                if k > sched.steps:
                    continue
                sk = shard_skew(sched, k)
                if sk > 1.05:
                    # ceil(S/k)/(S/k) is the optimum for S steps; tiny
                    # cells (S < ~20k) cannot meet the 1.05 regime with
                    # ANY partition — log, don't record.
                    print(f"# SHARD_SKEW skip m={m} n={n} k={k}: "
                          f"S={sched.steps} too small (optimal skew "
                          f"{sk:.3f} > 1.05)")
                    continue
                row = {
                    "test": "SHARD_SKEW", "map": kind, "m": m, "n": n,
                    "grid_steps": sched.steps, "shards": k,
                    "skew": sk,
                }
                if k <= nb:  # the slab baseline needs k nonempty layers
                    row["slab_skew"] = slab_skew(m, nb, k)
                if (m, n) in ca_cells:
                    mesh = (shard_mesh(k)
                            if jax.device_count() >= k else None)
                    rng = np.random.default_rng(m * 10 + k)
                    s = (rng.random((n,) * m) < 0.4).astype(np.int32)
                    s = np.where(np.asarray(Ref.simplex_mask(m, n)), s, 0)
                    s = s.astype(np.int32)
                    single = (simplex_ca2d if m == 2 else simplex_ca_md)(
                        s, kind=kind
                    )
                    shd = sharded_ca(s, k, kind=kind, mesh=mesh)
                    exact = bool(np.array_equal(
                        np.asarray(single), np.asarray(shd)
                    ))
                    if not exact:
                        raise SystemExit(
                            f"SHARD_SKEW bit-exactness FAILED: m={m} "
                            f"n={n} kind={kind} shards={k}"
                        )
                    row["bit_exact"] = exact
                    row["devices"] = jax.device_count()
                rows.append(row)
    return rows


def write_maps_artifact(rows, path: str = "BENCH_maps.json") -> str:
    """Persist steps/waste/wall-time per (kind, m, n) for perf tracking.

    Schema bench-maps/v2: every row additionally records the backend it
    ran on, the JAX version, and whether it went down the compiled path
    (fused-XLA / non-interpret Pallas) or the interpret emulator — so
    the autotuner and future-PR perf diffs never mix the two regimes.
    """
    import jax

    from repro.kernels.policy import backend_name

    backend = backend_name()
    jax_version = jax.__version__
    artifact = {
        "schema": "bench-maps/v2",
        "rows": [
            {
                "test": r.get("test"),
                "map": r.get("map"),
                "m": r.get("m"),
                "n": r.get("n"),
                "grid_steps": r.get("grid_steps"),
                "waste": r.get("waste"),
                "us_per_call": (
                    None
                    if r.get("us_per_call") is None
                    or (isinstance(r.get("us_per_call"), float)
                        and math.isnan(r["us_per_call"]))
                    else r["us_per_call"]
                ),
                "backend": backend,
                "jax_version": jax_version,
                "compiled": bool(r.get("compiled", False)),
                **(
                    {"autotune_source": r["autotune_source"]}
                    if "autotune_source" in r
                    else {}
                ),
                **({"body": r["body"]} if "body" in r else {}),
                **(
                    {"max_abs_err": r["max_abs_err"]}
                    if "max_abs_err" in r
                    else {}
                ),
                **{
                    key: r[key]
                    for key in ("shards", "skew", "slab_skew",
                                "bit_exact", "devices",
                                "seq", "batch", "heads", "head_dim",
                                "step_ratio", "tok_s_prefill",
                                "tok_s_decode")
                    if key in r
                },
            }
            for r in rows
            if "grid_steps" in r
        ],
    }
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    return os.path.abspath(path)


def validate_artifact(path: str) -> None:
    """Fail (SystemExit) unless the artifact is well-formed v2 with at
    least one compiled row — the schema gate the CI smoke job runs.

    When ATTN rows are present (the serving metric — DESIGN.md §8),
    additionally require all three executor kinds {bb, folded, chunked}
    with positive prefill tokens/s per kind.
    """
    with open(path) as f:
        artifact = json.load(f)
    if artifact.get("schema") != "bench-maps/v2":
        raise SystemExit(f"bad schema: {artifact.get('schema')!r}")
    rows = artifact.get("rows", [])
    required = ("test", "map", "m", "n", "grid_steps", "backend",
                "jax_version", "compiled")
    for r in rows:
        missing = [k for k in required if k not in r]
        if missing:
            raise SystemExit(f"row missing {missing}: {r}")
    if not any(r["compiled"] for r in rows):
        raise SystemExit("no compiled rows in artifact")
    attn = [r for r in rows if r["test"] == "ATTN"]
    if attn:
        kinds = {r["map"] for r in attn}
        if not {"bb", "folded", "chunked"} <= kinds:
            raise SystemExit(f"ATTN rows missing kinds: {sorted(kinds)}")
        for r in attn:
            if not r.get("tok_s_prefill", 0) > 0:
                raise SystemExit(f"ATTN row without tokens/s: {r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick", action="store_true",
        help="CI smoke mode: compiled rows + schedule builds only "
             "(skips the interpret-mode kernel sweeps), then validates "
             "the written artifact",
    )
    ap.add_argument(
        "--out", default=None,
        help="artifact path (default BENCH_maps.json; "
             "BENCH_maps.quick.json under --quick)",
    )
    ns = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = ns.out or ("BENCH_maps.quick.json" if ns.quick else
                     "BENCH_maps.json")

    t0 = time.time()
    if ns.quick:
        print("# ==== compiled execution (autotuned kinds) ====")
        rcomp = _compiled_rows(quick=True)
        for r in rcomp:
            print(f"{r['test']},{r['map']},{r['grid_steps']},"
                  f"{r['us_per_call']:.0f},src={r.get('autotune_source', '-')}")
        print("# ==== §4.2: composite vs table (host build) ====")
        rc = _composite_rows()
        print("# ==== engine parity (differential: engine vs legacy/ref) ====")
        rp = _engine_parity_rows(quick=True)
        for r in rp:
            print(f"{r['test']},{r['body']},{r['map']},m={r['m']},"
                  f"err={r['max_abs_err']:.2e}")
        print("# ==== §7: sharded simplex execution (fold skew) ====")
        rs = _shard_rows(quick=True)
        for r in rs:
            print(f"{r['test']},{r['map']},m={r['m']},n={r['n']},"
                  f"k={r['shards']},skew={r['skew']:.4f},"
                  f"slab={r.get('slab_skew', float('nan')):.3f},"
                  f"bit_exact={r.get('bit_exact', '-')}")
        print("# ==== §8: serving attention (tokens/s per executor) ====")
        from . import bench_attention
        ratt = bench_attention.serving_rows(quick=True)
        for r in ratt:
            print(f"{r['test']},{r['map']},steps={r['grid_steps']},"
                  f"tok_s_prefill={r['tok_s_prefill']:.0f},"
                  f"tok_s_decode={r.get('tok_s_decode', float('nan')):.0f},"
                  f"step_ratio={r['step_ratio']:.2f}")
        path = write_maps_artifact(rcomp + rc + rp + rs + ratt, path=out)
        validate_artifact(path)
        print(f"# wrote + validated {path}")
        print(f"# total {time.time()-t0:.0f}s")
        return

    from . import (
        bench_attention,
        bench_energy,
        bench_general_m,
        bench_maps_2simplex,
        bench_maps_3simplex,
    )

    print("# ==== Fig.10: 2-simplex maps ====")
    r2 = bench_maps_2simplex.main()
    print("# ==== Fig.13: 3-simplex maps ====")
    r3 = bench_maps_3simplex.main()
    print("# ==== beyond-paper: general-m (m=4) schedules ====")
    rm = _map_rows_md()
    for r in rm:
        print(f"{r['test']},{r['map']},{r['grid_steps']},{r['waste']:.3f},"
              f"{r['us_per_call']:.0f}")
    print("# ==== §4.2: composite vs table at non-pow2 n (host build) ====")
    rc = _composite_rows()
    for r in rc:
        print(f"{r['test']},{r['map']},n={r['n']},{r['grid_steps']},"
              f"{r['waste']:.3f},build_us={r['us_per_call']:.0f}")
    print("# ==== compiled execution (autotuned kinds) ====")
    rcomp = _compiled_rows()
    for r in rcomp:
        print(f"{r['test']},{r['map']},{r['grid_steps']},"
              f"{r['us_per_call']:.0f},src={r.get('autotune_source', '-')}")
    print("# ==== engine parity (differential: engine vs legacy/ref) ====")
    rp = _engine_parity_rows()
    for r in rp:
        print(f"{r['test']},{r['body']},{r['map']},m={r['m']},"
              f"err={r['max_abs_err']:.2e}")
    print("# ==== §7: sharded simplex execution (fold skew) ====")
    rs = _shard_rows()
    for r in rs:
        print(f"{r['test']},{r['map']},m={r['m']},n={r['n']},"
              f"k={r['shards']},skew={r['skew']:.4f},"
              f"slab={r.get('slab_skew', float('nan')):.3f},"
              f"bit_exact={r.get('bit_exact', '-')}")
    print("# ==== Fig.12/15: energy (modeled) ====")
    re = bench_energy.main()
    print("# ==== §6: general-m (r,beta) ====")
    rg = bench_general_m.main()
    print("# ==== beyond-paper: folded causal attention ====")
    ra = bench_attention.main()
    print("# ==== §8: serving attention (tokens/s per executor) ====")
    ratt = bench_attention.serving_rows()
    for r in ratt:
        print(f"{r['test']},{r['map']},n={r['n']},steps={r['grid_steps']},"
              f"tok_s_prefill={r['tok_s_prefill']:.0f},"
              f"tok_s_decode={r.get('tok_s_decode', float('nan')):.0f},"
              f"step_ratio={r['step_ratio']:.2f}")

    path = write_maps_artifact(
        r2 + r3 + rm + rc + rcomp + rp + rs + ratt, path=out
    )
    validate_artifact(path)
    print(f"# wrote + validated {path}")

    print("# ==== summary: name,us_per_call,derived ====")
    for r in r2:
        print(f"fig10/{r['test']}/{r['map']},{r['us_per_call']:.0f},"
              f"space_speedup={r['space_speedup_vs_bb']:.3f}")
    for r in r3:
        us = r["us_per_call"]
        print(f"fig13/{r['test']}/{r['map']},"
              f"{us if not math.isnan(us) else 0:.0f},"
              f"space_speedup={r['space_speedup_vs_bb']:.3f}")
    for r in rm:
        print(f"md/{r['test']}/{r['map']},{r['us_per_call']:.0f},"
              f"space_speedup={r['space_speedup_vs_bb']:.3f}")
    for r in rc:
        print(f"sched/{r['test']}/{r['map']}/n={r['n']},"
              f"{r['us_per_call']:.0f},waste={r['waste']:.3f}")
    for r in rcomp:
        print(f"compiled/{r['test']}/{r['map']},{r['us_per_call']:.0f},"
              f"autotune={r.get('autotune_source', '-')}")
    for r in rs:
        print(f"shard/m={r['m']}/n={r['n']}/k={r['shards']},0,"
              f"skew={r['skew']:.4f}")
    for r in re:
        print(f"fig12/{r['test']}/{r['map']},0,"
              f"eps_per_w_vs_bb={r['eps_per_w_vs_bb']:.2f}")
    for r in rg:
        print(f"sec6/m={r['m']},0,speedup={r['speedup_vs_bb']:.1f}")
    for r in ra:
        print(f"attn/{r['shape']},{r['folded_us']:.0f},"
              f"wall_speedup={r['wall_speedup']:.2f}")
    for r in ratt:
        print(f"serve-attn/{r['map']}/s={r['seq']},{r['us_per_call']:.0f},"
              f"tok_s_prefill={r['tok_s_prefill']:.0f}")
    print(f"# total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
