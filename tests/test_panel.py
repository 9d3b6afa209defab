"""Wide-geometry block-panel kernel tests (docs/design.md §14).

Covers the K-tiled panel matmul's byte identity vs golden host
arithmetic (dispatch-level across fields, incl. uneven tails), the
XOR-abelian K-block permutation property, the VMEM estimator's
accept/reject calibration boundaries, the three-way tier decision
(no supported geometry raises — it only routes), the geometry-sweep
recompile-flatness acceptance, the packed GF(2^16) byte-sliced decode,
and the mesh tier's zero-reshard contract on panel-routed programs.

The heaviest geometries (RS(200,56) and the wide-field RS(100,30) —
multi-hundred-k-op networks that cost minutes to trace + compile on
the interpret backend) are ``slow``-marked; tier-1 keeps the panel
route honest on geometries whose networks trace in seconds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noise_ec_tpu.gf import gf2_matmul_planes
from noise_ec_tpu.gf.bitmatrix import expand_generator_bits
from noise_ec_tpu.gf.field import GF256, GF65536
from noise_ec_tpu.golden.codec import GoldenCodec
from noise_ec_tpu.matrix.generators import generator_matrix
from noise_ec_tpu.obs.registry import default_registry
from noise_ec_tpu.ops.dispatch import DeviceCodec
from noise_ec_tpu.ops.pallas_gf2mm import (
    PANEL_XOR_BUDGET,
    VMEM_BUDGET_BYTES,
    bits_to_rows,
    gf2_matmul_pallas_panel_rows,
    panel_plan,
    panel_temp_cap,
    panel_vmem_bytes,
    planes_to_tiled,
    sparse_lane_tl,
    tiled_to_planes,
)
from noise_ec_tpu.ops.xor_factor import (
    factor_panels,
    split_bits_rows_panels,
    xor_cost,
)


# ------------------------------------------------- kernel-level identity


def test_panel_matmul_matches_planes_reference(rng):
    """Byte identity vs the numpy planes reference on an uneven
    geometry (R, C, W all non-multiples of every block size), with an
    empty output row, across several forced tile plans including ones
    that exercise multi-panel K and R axes."""
    bits = rng.integers(0, 2, size=(19, 45)).astype(np.uint8)
    bits[3] = 0  # empty-row path
    planes = rng.integers(0, 2**32, size=(45, 777), dtype=np.uint32)
    want = gf2_matmul_planes(bits, planes)
    tiled = planes_to_tiled(jnp.asarray(planes))
    rows = bits_to_rows(bits)
    for plan in (None, (16, 8, 128, 512), (8, 4, 128, 64)):
        out = gf2_matmul_pallas_panel_rows(
            rows, tiled, plan=plan, interpret=True
        )
        got = np.asarray(tiled_to_planes(out, 777))
        np.testing.assert_array_equal(got, want)


def test_panel_kblock_accumulation_order_invariance(rng):
    """XOR is abelian: permuting the K-block assignment (which panel's
    partial lands in which accumulation step) must not change a single
    byte. The permutation renumbers whole KB-column blocks of the
    network and moves the matching input row blocks, so the K-step
    accumulation order over the output tile genuinely differs."""
    KB = 8
    bits = rng.integers(0, 2, size=(11, 45)).astype(np.uint8)
    planes = rng.integers(0, 2**32, size=(45, 300), dtype=np.uint32)
    want = gf2_matmul_planes(bits, planes)
    rows = bits_to_rows(bits)
    nb = -(-45 // KB)
    plan = (KB, 4, 128, 64)
    for seed in (1, 2):
        perm = np.random.default_rng(seed).permutation(nb)
        pos = {int(oldb): newb for newb, oldb in enumerate(perm)}
        planes_full = np.zeros((nb * KB, 300), np.uint32)
        planes_full[:45] = planes
        planes_p = np.concatenate(
            [planes_full[b * KB : (b + 1) * KB] for b in perm]
        )
        rows_p = tuple(
            tuple(sorted(pos[c // KB] * KB + c % KB for c in row))
            for row in rows
        )
        out = gf2_matmul_pallas_panel_rows(
            rows_p, planes_to_tiled(jnp.asarray(planes_p)), plan=plan,
            interpret=True,
        )
        got = np.asarray(tiled_to_planes(out, 300))
        np.testing.assert_array_equal(got, want)


# ------------------------------------- VMEM estimator calibration pins


def test_temp_model_boundary_cases():
    """The calibration anchors from the estimator comments, pinned so a
    recalibration cannot silently OOM a launch.

    Whole-plane model (TEMP_ALIVE_FRACTION = 0.4): RS(50,20)'s factored
    network at TL=256 OOMed at 24.7M scoped on hardware — the model
    must REJECT 256 (pick 128); the same model must ACCEPT wide tiles
    for a compact RS(10,4)-class network.

    Panel model (PANEL_TEMP_ALIVE_FRACTION = 1.0, cap-based): a tile
    triple whose blocks alone exceed the budget yields a non-positive
    temp cap (REJECT — the planner must never emit it), and every plan
    the auto-tuner emits must fit the budget at its own cap (ACCEPT),
    with the per-panel factoring's actual temp usage bounded by the
    cap it was given.
    """
    gf = GF256()
    g50 = generator_matrix(gf, 50, 70, "cauchy")
    rows50 = bits_to_rows(expand_generator_bits(gf, g50[50:]))
    assert sparse_lane_tl(rows50, 400, 10**6) == 128  # reject TL>=256
    g10 = generator_matrix(gf, 10, 14, "cauchy")
    rows10 = bits_to_rows(expand_generator_bits(gf, g10[10:]))
    assert sparse_lane_tl(rows10, 80, 10**6) == 512  # accept wide tile

    # Panel reject boundary: (256, 256, 512) blocks = 16.8M > 14M.
    assert panel_temp_cap(256, 256, 512) <= 0
    # Panel accept boundary + cap enforcement on a real wide geometry.
    g120 = generator_matrix(gf, 120, 124, "cauchy")
    rows120 = bits_to_rows(expand_generator_bits(gf, g120[120:]))
    plan = panel_plan(rows120, 8 * 120)
    KB, RB, TL, cap = plan[:4]
    assert cap > 0
    assert panel_vmem_bytes(KB, RB, TL, cap) <= VMEM_BUDGET_BYTES
    panels = split_bits_rows_panels(
        rows120, -(-8 * 120 // KB) * KB, KB, RB
    )
    _total, worst = factor_panels(panels, KB, max_temps=cap)
    assert 0 < worst <= cap


# ----------------------------------------------- tier decision routing


def test_tier_decision_routes_every_supported_geometry():
    """The old RS(200,56) "must not even attempt" planning guard is a
    tier decision now: across the supported range (k <= n <= 256, both
    fields) nothing raises — route_for answers baked/panel/mxu, and
    panel-routed matrices get a VMEM-fitting plan. On the compiled
    `pallas` kernel the panel budget covers RS(200,56) encode AND its
    decode1 fold; the interpret kernel keeps those on the MXU route
    (multi-minute trace/compile is useless for CPU correctness runs),
    which test_ops pins."""
    from noise_ec_tpu.ops.dispatch import decode1_fold_matrix

    for field, geoms in (
        ("gf256", ((5, 3), (17, 3), (50, 20), (100, 30), (200, 56),
                   (255, 1), (3, 200))),
        ("gf65536", ((5, 3), (50, 4), (100, 30), (200, 56))),
    ):
        dev = DeviceCodec(field=field, kernel="pallas")
        for k, r in geoms:
            if k + r > 256 and field == "gf256":
                continue
            M = generator_matrix(dev.gf, k, min(256, k + r), "cauchy")[k:]
            route = dev.route_for(M)
            assert route in ("baked", "panel", "mxu"), (field, k, r)
            if route == "panel":
                KB, RB, TL, cap = panel_plan(
                    dev.bits_rows_for(M), dev.gf.degree * k
                )[:4]
                assert panel_vmem_bytes(KB, RB, TL, cap) <= VMEM_BUDGET_BYTES
    dev = DeviceCodec(field="gf256", kernel="pallas")
    G = generator_matrix(dev.gf, 200, 256, "cauchy")
    assert dev.route_for(G[200:]) == "panel"
    assert xor_cost(dev.bits_rows_for(G[200:])) <= PANEL_XOR_BUDGET
    # The ISSUE-15 acceptance: the program-size model splits the
    # ~361k-XOR RS(200,56) network across G > 1 K-grid sub-launches
    # (one Mosaic program per K-slice) instead of leaving the single
    # over-limit program to the probe's MXU demotion; the wide-field
    # RS(100,30) network — RS(200,56)-sized in byte rows — splits too.
    assert panel_plan(dev.bits_rows_for(G[200:]), 8 * 200)[4] > 1
    dev16w = DeviceCodec(field="gf65536", kernel="pallas")
    G16w = generator_matrix(dev16w.gf, 100, 130, "cauchy")
    assert dev16w.route_for(G16w[100:]) == "panel"
    assert panel_plan(
        dev16w.bits_rows_for(G16w[100:]), 16 * 100
    )[4] > 1
    # The fused corrupted-share decode fold rides the panel tier too.
    from noise_ec_tpu.matrix.linalg import gf_inv

    A = dev.gf.matmul(
        G[200:].astype(np.int64), gf_inv(dev.gf, G[:200]).astype(np.int64)
    ).astype(np.uint8)
    D = decode1_fold_matrix(dev.gf, A, 1)
    assert dev.route_for(D) == "panel"
    # Past every XOR budget: the wide-field near-limit expansion (~1.4M
    # raw XORs) still routes — to the MXU — instead of raising.
    dev16 = DeviceCodec(field="gf65536", kernel="pallas")
    G16 = generator_matrix(dev16.gf, 200, 256, "cauchy")
    assert dev16.route_for(G16[200:]) == "mxu"


# ------------------------------------------ dispatch-level byte identity


def test_panel_dispatch_byte_identity_gf256(rng):
    """RS(120,4) — wide-row geometry on the natural panel route (rows
    past the whole-plane pack bound, network under every budget) —
    through the public dispatch, uneven tail, vs the golden codec; the
    tile telemetry must attribute the dispatch to the plan's label."""
    k, r = 120, 4
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    G = generator_matrix(dev.gf, k, k + r, "cauchy")
    assert dev.route_for(G[k:]) == "panel"
    D = rng.integers(0, 256, size=(k, 3001)).astype(np.uint8)
    got = dev.matmul_stripes(G[k:], D)
    want = np.asarray(GoldenCodec(k, k + r).encode(D))
    np.testing.assert_array_equal(got, want)
    from noise_ec_tpu.ops.dispatch import tile_label

    label = tile_label(dev.panel_plan_for(G[k:]))
    tile_calls = default_registry().counter(
        "noise_ec_kernel_tile_dispatches_total"
    ).labels(entry="matmul_stripes_pallas_interpret", tile=label)
    assert tile_calls.value >= 1


def test_panel_dispatch_byte_identity_gf65536(rng):
    """Wide-field RS(50,4) — 100 byte rows push it past the whole-plane
    row bound onto the panel tier via the PACKED byte-sliced layout —
    through the public dispatch, uneven tail, vs the golden codec."""
    k, r = 50, 4
    dev = DeviceCodec(field="gf65536", kernel="pallas_interpret")
    G = generator_matrix(dev.gf, k, k + r, "cauchy")
    assert dev.route_for(G[k:]) == "panel"
    D = rng.integers(0, 1 << 16, size=(k, 501)).astype(np.uint16)
    got = dev.matmul_stripes(G[k:], D)
    want = np.asarray(GoldenCodec(k, k + r, field="gf65536").encode(D))
    np.testing.assert_array_equal(got, want)


def test_panel_words_pipeline_rs50_20_identity(rng):
    """RS(50,20) normally rides the whole-plane route; forcing its
    network through the panel words pipeline (explicit plan) must be
    byte-identical — the two tiers implement one layout contract and
    the planner may move a geometry between them as budgets move."""
    from noise_ec_tpu.ops.dispatch import _panel_words_fn

    gf = GF256()
    k, r = 50, 20
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    assert dev.route_for(G[k:]) == "baked"
    bits_rows = dev.bits_rows_for(G[k:])
    plan = panel_plan(bits_rows, 8 * k)
    TW = 8192
    words = rng.integers(
        0, 1 << 32, size=(k, TW), dtype=np.uint64
    ).astype(np.uint32)
    fn = _panel_words_fn(r, 8, bits_rows, plan, True)
    got = np.asarray(fn(jnp.asarray(words)))
    want_sym = gf.matvec_stripes(
        G[k:], words.view(np.uint8).reshape(k, -1)
    )
    np.testing.assert_array_equal(
        got.view(np.uint8).reshape(r, -1), want_sym
    )


# ----------------------------------------------- recompile-churn guard


def test_panel_geometry_sweep_no_recompile_churn(rng):
    """The PR-5 acceptance pattern on the panel tier: a repeated
    geometry sweep must add ZERO compile-route dispatches the second
    time around — the plan is deterministic and part of the cache key,
    so warm panel traffic never re-jits."""
    compiles = default_registry().counter("noise_ec_jit_compiles_total")

    def total():
        return sum(c.value for _, c in compiles.children())

    dev8 = DeviceCodec(field="gf256", kernel="pallas_interpret")
    dev16 = DeviceCodec(field="gf65536", kernel="pallas_interpret")
    G8 = generator_matrix(dev8.gf, 120, 124, "cauchy")
    G16 = generator_matrix(dev16.gf, 50, 54, "cauchy")
    D8 = rng.integers(0, 256, size=(120, 3001)).astype(np.uint8)
    D16 = rng.integers(0, 1 << 16, size=(50, 501)).astype(np.uint16)

    def sweep():
        dev8.matmul_stripes(G8[120:], D8)
        dev16.matmul_stripes(G16[50:], D16)

    sweep()  # first sweep may compile (fresh keys)
    warm = total()
    sweep()
    sweep()
    assert total() == warm, "repeat panel geometry sweep re-compiled"


# ------------------------------------------- K-grid sub-launch splitting


def test_sublaunch_split_byte_identity(rng):
    """Split-vs-single-launch byte identity (docs/design.md §14
    "Sub-launch splitting"): forced G ∈ {2, 3, 4} over a geometry with
    an uneven K tail (C=45 at KB=8 → PK=6 with a 5-row tail block, and
    a K-block count that does not divide evenly into any G) must match
    the single-launch kernel and the numpy planes reference byte for
    byte — the accumulator chain changes the evaluation order only,
    and XOR is abelian."""
    bits = rng.integers(0, 2, size=(19, 45)).astype(np.uint8)
    bits[7] = 0  # empty-row path through the accumulating kernel too
    planes = rng.integers(0, 2**32, size=(45, 777), dtype=np.uint32)
    want = gf2_matmul_planes(bits, planes)
    tiled = planes_to_tiled(jnp.asarray(planes))
    rows = bits_to_rows(bits)
    single = np.asarray(tiled_to_planes(
        gf2_matmul_pallas_panel_rows(
            rows, tiled, plan=(8, 4, 128, 64, 1), interpret=True
        ), 777,
    ))
    np.testing.assert_array_equal(single, want)
    for G in (2, 3, 4):
        out = gf2_matmul_pallas_panel_rows(
            rows, tiled, plan=(8, 4, 128, 64, G), interpret=True
        )
        got = np.asarray(tiled_to_planes(out, 777))
        np.testing.assert_array_equal(got, want)
    # G past PK clamps to one K-block per launch instead of erroring;
    # a legacy 4-tuple plan means G=1.
    for plan in ((8, 4, 128, 64, 99), (8, 4, 128, 64)):
        out = gf2_matmul_pallas_panel_rows(
            rows, tiled, plan=plan, interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(tiled_to_planes(out, 777)), want
        )


def test_sublaunch_program_size_model_boundaries():
    """The program-size model's G boundary, pinned in the model's own
    currency (raw XORs — deliberately ratio-free so this boundary is
    deterministic): the largest G=1 network (raw == budget) stays a
    single launch, one more XOR splits to G=2, and G is clamped to the
    K-block count."""
    from noise_ec_tpu.ops.pallas_gf2mm import (
        PANEL_SUBLAUNCH_XOR_BUDGET,
        sublaunch_bounds,
        sublaunch_count,
    )

    B = PANEL_SUBLAUNCH_XOR_BUDGET
    assert sublaunch_count(B, PK=64) == 1        # largest single launch
    assert sublaunch_count(B + 1, PK=64) == 2    # smallest split
    assert sublaunch_count(3 * B, PK=64) == 3
    assert sublaunch_count(10**9, PK=7) == 7     # clamped to K-blocks
    # Bounds: contiguous, exhaustive, every chunk non-empty.
    for PK, G in ((7, 3), (6, 4), (12, 5), (3, 3)):
        bounds = sublaunch_bounds(PK, G)
        assert bounds[0] == 0 and bounds[-1] == PK
        assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    # Through panel_plan itself: a synthetic network of exactly the
    # budget's raw cost plans G=1, one extra term plans G=2 (the
    # model's G rides the plan tuple, index 4).
    R, T = 16, 8126
    rows_flat = tuple(tuple(range(T)) for _ in range(R))
    assert xor_cost(rows_flat) == R * (T - 1) == 130_000 == B
    assert panel_plan(rows_flat, T)[4] == 1
    rows_over = (tuple(range(T)),) * (R - 1) + (
        tuple(range(T)), (0, 1),
    )
    assert xor_cost(rows_over) == B + 1
    assert panel_plan(rows_over, T)[4] == 2


def test_sublaunch_probe_escalation_and_final_demotion(monkeypatch):
    """The demote-to-MXU branch fires only when even G = K-blocks fails
    the probe: a Mosaic rejection first ESCALATES G (doubling, capped
    at PK), and panel_plan_for returns the escalated plan as soon as a
    split compiles."""
    import noise_ec_tpu.ops.dispatch as dispatch_mod
    from noise_ec_tpu.matrix.generators import generator_matrix as genm

    dev = DeviceCodec(field="gf256", kernel="pallas")
    M = genm(dev.gf, 120, 124, "cauchy")[120:]
    assert dev.route_for(M) == "panel"
    base = panel_plan(dev.bits_rows_for(M), 8 * 120)
    PK = -(-8 * 120 // base[0])
    assert PK >= 4  # the escalation below needs room to double
    probed = []

    def fake_probe(bits_rows, C, plan):
        probed.append(plan[4])
        return plan[4] >= 4  # Mosaic "accepts" only >= 4 sub-launches

    monkeypatch.setattr(dispatch_mod, "_panel_probe_compiles", fake_probe)
    plan = dev.panel_plan_for(M)
    assert plan is not None and plan[4] == 4
    assert probed == [base[4], 2, 4] or probed == [base[4], 4]
    # Nothing compiles, even one K-block per launch: NOW demote.
    probed.clear()
    monkeypatch.setattr(
        dispatch_mod, "_panel_probe_compiles", lambda *a: False
    )
    assert dev.panel_plan_for(M) is None
    assert probed == []  # lambda records nothing; demotion = None
    assert dev._route_plan(M) == ("mxu", None)


def test_sublaunch_dispatch_telemetry_and_cache_key(rng, monkeypatch):
    """A panel dispatch under a G-way plan is byte-identical through
    the public entry, adds G to the sub-launch dispatch counter, and
    G is part of the program's cache key (a G change builds a new
    program, so its first dispatch compiles and reads as a
    compile-route dispatch, not a silent re-time)."""
    import noise_ec_tpu.ops.dispatch as dispatch_mod

    k, r = 120, 4
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    G = generator_matrix(dev.gf, k, k + r, "cauchy")
    assert dev.route_for(G[k:]) == "panel"
    base = panel_plan(dev.bits_rows_for(G[k:]), 8 * k)
    forced = base[:4] + (2,)
    monkeypatch.setattr(
        dispatch_mod, "panel_plan", lambda bits_rows, C: forced
    )
    bits = dev.bits_rows_for(G[k:])
    prog1 = dispatch_mod._panel_words_fn(r, 8, bits, forced, True, True)
    D = rng.integers(0, 256, size=(k, 3001)).astype(np.uint8)
    subs = default_registry().counter(
        "noise_ec_kernel_sublaunch_dispatches_total"
    ).labels(entry="matmul_stripes_pallas_interpret")
    before = subs.value
    got = dev.matmul_stripes(G[k:], D)
    want = np.asarray(GoldenCodec(k, k + r).encode(D))
    np.testing.assert_array_equal(got, want)
    assert subs.value == before + 2
    # Program-side count: the split built at least 2 distinct programs
    # (initial + accumulating) across the run.
    progs = default_registry().counter(
        "noise_ec_kernel_sublaunch_programs_total"
    ).labels()
    assert progs.value >= 2
    monkeypatch.setattr(
        dispatch_mod, "panel_plan", lambda bits_rows, C: base[:4] + (3,)
    )
    prog2 = dispatch_mod._panel_words_fn(
        r, 8, bits, base[:4] + (3,), True, True
    )
    assert prog2 is not prog1  # G rides the program cache key


def test_mesh_sublaunch_split_zero_reshard(rng, monkeypatch):
    """The mesh tier under a G-way split plan: the sub-launch chain
    runs INSIDE the per-shard shard_map body, so sharded wide-geometry
    encode stays byte-identical and noise_ec_mesh_reshard_total does
    not move — the zero-reshard contract holds across sub-launches."""
    import noise_ec_tpu.ops.dispatch as dispatch_mod
    from noise_ec_tpu.parallel.mesh import (
        configure_mesh_router,
        reset_mesh_router,
    )

    k, r = 120, 4
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    G = generator_matrix(dev.gf, k, k + r, "cauchy")
    base = panel_plan(dev.bits_rows_for(G[k:]), 8 * k)
    monkeypatch.setattr(
        dispatch_mod, "panel_plan", lambda bits_rows, C: base[:4] + (2,)
    )
    router = configure_mesh_router(enable=True)
    try:
        assert router.enabled
        B, TW = 8, 8192
        words = rng.integers(
            0, 1 << 32, size=(B, k, TW), dtype=np.uint64
        ).astype(np.uint32)
        reshard = default_registry().counter("noise_ec_mesh_reshard_total")
        reshard0 = reshard.labels().value
        subs = default_registry().counter(
            "noise_ec_kernel_sublaunch_dispatches_total"
        ).labels(entry="mesh_words")
        subs0 = subs.value
        parity = router.matmul_words_batch(dev, G[k:], words)
        assert reshard.labels().value == reshard0
        assert subs.value == subs0 + 2
        want0 = dev.gf.matvec_stripes(
            G[k:], words[0].view(np.uint8).reshape(k, -1)
        )
        np.testing.assert_array_equal(
            np.asarray(parity)[0].view(np.uint8).reshape(r, -1), want0
        )
    finally:
        reset_mesh_router()


# ------------------------------------------ persistent compile cache


def test_compile_cache_repeat_sweep_zero_recompile(rng, tmp_path):
    """The compile-churn guard with the persistent cache armed: with
    JAX_COMPILATION_CACHE_DIR placing the cache (config mirror of the
    env var), default_compile_cache keeps that directory, a repeated
    panel geometry sweep adds ZERO compile-route dispatches — and the
    directory holds serialized executables for the sweep's programs."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from noise_ec_tpu.ops.dispatch import default_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert default_compile_cache() == str(tmp_path)
    try:
        compiles = default_registry().counter("noise_ec_jit_compiles_total")

        def total():
            return sum(c.value for _, c in compiles.children())

        # A geometry + shape no other test touches: the cache-write
        # assertion needs this sweep's FIRST dispatch to really compile
        # (a jit-warm program from an earlier test would write nothing).
        dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
        G = generator_matrix(dev.gf, 119, 123, "cauchy")
        D = rng.integers(0, 256, size=(119, 2777)).astype(np.uint8)

        def sweep():
            dev.matmul_stripes(G[119:], D)

        sweep()
        warm = total()
        sweep()
        sweep()
        assert total() == warm, "repeat sweep re-compiled with cache on"
        assert any(tmp_path.iterdir()), "persistent cache wrote no programs"
    finally:
        # Un-arm: later tests must not keep serializing into tmp_path.
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()


def test_default_compile_cache_fixed_path_without_env(tmp_path,
                                                     monkeypatch):
    """No JAX_COMPILATION_CACHE_DIR: default_compile_cache places the
    cache at the fixed in-checkout path (DEFAULT_CACHE_DIR — redirected
    to tmp_path here so the test writes nothing into the repo) with the
    size/time floors zeroed."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    import noise_ec_tpu.ops.dispatch as dispatch_mod

    assert dispatch_mod.DEFAULT_CACHE_DIR.endswith(".jax_cache")
    monkeypatch.setattr(dispatch_mod, "DEFAULT_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        assert dispatch_mod.default_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()


def test_compile_cache_hit_counter():
    """The jax.monitoring bridge: cache-hit events land in
    noise_ec_compile_cache_hits_total; unrelated events do not."""
    from noise_ec_tpu.ops.dispatch import _note_cache_event

    hits = default_registry().counter(
        "noise_ec_compile_cache_hits_total"
    ).labels()
    before = hits.value
    _note_cache_event("/jax/compilation_cache/cache_hits")
    assert hits.value == before + 1
    _note_cache_event("/jax/compilation_cache/cache_misses")
    _note_cache_event("/jax/pjit/compile")
    assert hits.value == before + 1


def test_prewarm_ladder_compiles_batch_rungs(rng):
    """The ladder pre-warm hook compiles every power-of-two batch rung
    for a geometry (1, 2, 4, 8) without error and reports the count —
    the set the persistent cache replays after a restart."""
    from noise_ec_tpu.ops.dispatch import prewarm_ladder

    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    G = generator_matrix(dev.gf, 10, 14, "cauchy")
    assert prewarm_ladder(dev, G[10:], stripe_bytes=256, max_batch=8) == 4
    # Warmed: an immediate batch dispatch at a ladder size re-jits
    # nothing (the in-process cache holds every rung's program).
    compiles = default_registry().counter("noise_ec_jit_compiles_total")
    warm = sum(c.value for _, c in compiles.children())
    Ds = [rng.integers(0, 256, size=(10, 256)).astype(np.uint8)
          for _ in range(4)]
    outs = dev.matmul_stripes_many(G[10:], Ds)
    assert sum(c.value for _, c in compiles.children()) == warm
    want = np.asarray(GoldenCodec(10, 14).encode(Ds[0]))
    np.testing.assert_array_equal(outs[0], want)


# ----------------------------------------------- bench_gate panel bars


def _bench_gate():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        import bench_gate
    finally:
        sys.path.pop(0)
    return bench_gate


def test_panel_rig_check_bars(tmp_path):
    """panel_rig_check (the ISSUE-15 guard): on a rig with a MULTICHIP
    record, the PR-10 bars bite — rs200_56 route off panel, encode
    under 150 GB/s, or a wide-field decode ratio over 1.25 each flag;
    a green run and a recordless dev box do not."""
    from pathlib import Path

    bg = _bench_gate()
    rig = Path(__file__).resolve().parent / "data" / "bench_gate"
    assert bg.newest_multichip_devices(rig) == 8  # the fixture rig
    good = {
        "rs200_56_route": "panel",
        "rs200_56_sublaunches": 3,
        "rs200_56_encode_gbps": 163.0,
        "gf65536_vs_gf256_decode_ratio": 1.1,
    }
    assert bg.panel_rig_check(good, rig) == []
    assert len(bg.panel_rig_check({
        "rs200_56_route": "mxu",
        "rs200_56_encode_gbps": 38.4,
        "gf65536_vs_gf256_decode_ratio": 1.6,
    }, rig)) == 3
    problems = bg.panel_rig_check(dict(good, rs200_56_encode_gbps=120.0), rig)
    assert len(problems) == 1 and "150" in problems[0]
    problems = bg.panel_rig_check(
        dict(good, gf65536_vs_gf256_decode_ratio=1.3), rig
    )
    assert len(problems) == 1 and "1.25" in problems[0]
    # Missing keys (recorded pre-panel rounds) stay green; a dev box
    # without a MULTICHIP record is exempt entirely.
    assert bg.panel_rig_check({}, rig) == []
    assert bg.panel_rig_check(
        {"rs200_56_route": "mxu"}, repo=tmp_path
    ) == []
    # The new stats keys never enter the regression compare: routes and
    # sub-launch counts are identity, not performance.
    assert bg.metric_direction("rs200_56_sublaunches") is None
    assert bg.metric_direction("rs200_56_route") is None


# --------------------------------------- packed GF(2^16) fused decode


def test_decode1_words_bytesliced_corrects_and_verifies(rng):
    """The packed byte-sliced fused corrupted-share decode: corrected
    row equals the pre-corruption truth with all-clean verify on a
    single corrupted share, and the verify OR goes nonzero when a
    second share defeats the single-support hypothesis. The wide-field
    fold matrix (108 byte rows) rides the panel tier."""
    from noise_ec_tpu.matrix.linalg import gf_inv
    from noise_ec_tpu.ops.pallas_pack import (
        unpack_u16_bytesliced,
        words16_to_bytesliced,
    )

    k, r = 50, 4
    dev = DeviceCodec(field="gf65536", kernel="pallas_interpret")
    gf = dev.gf
    G = generator_matrix(gf, k, k + r, "cauchy")
    data = rng.integers(0, 1 << 16, size=(k, 256)).astype(np.uint16)
    cw = np.asarray(
        GoldenCodec(k, k + r, field="gf65536").encode_all(data)
    )
    cw[1] ^= 0xA5A5  # whole-share corruption of data share 1
    A = gf.matmul(
        G[k:].astype(np.int64), gf_inv(gf, G[:k]).astype(np.int64)
    ).astype(np.uint16)
    assert dev.route_for(dev.decode1_matrix(A, 1)) == "panel"
    w = jnp.asarray(np.ascontiguousarray(cw).view("<u4"))
    bs = words16_to_bytesliced(w)
    corrected, bad = dev.decode1_words_bytesliced(A, 1, bs)
    got = unpack_u16_bytesliced(
        np.ascontiguousarray(np.asarray(corrected)).view(np.uint8)
    )
    np.testing.assert_array_equal(got[0], data[1])
    assert not np.asarray(bad).any()
    # Second corrupted share: the hypothesis must be defeated somewhere.
    cw2 = cw.copy()
    cw2[2, 7] ^= 0x0100
    bs2 = words16_to_bytesliced(
        jnp.asarray(np.ascontiguousarray(cw2).view("<u4"))
    )
    _, bad2 = dev.decode1_words_bytesliced(A, 1, bs2)
    assert np.asarray(bad2).any()


# --------------------------------------------- mesh tier, zero reshard


def test_mesh_panel_chained_encode_decode_zero_reshard(rng):
    """The mesh acceptance on PANEL-routed programs: sharded wide-
    geometry encode → on-device corruption → sharded fused decode1,
    out_shardings matching in_shardings all the way —
    noise_ec_mesh_reshard_total must not move, and bytes must match
    the single-device truth."""
    from noise_ec_tpu.parallel.mesh import (
        configure_mesh_router,
        reset_mesh_router,
    )

    router = configure_mesh_router(enable=True)
    try:
        assert router.enabled and router.n_pow2 == 8
        gf = GF256()
        k, r = 120, 4
        G = generator_matrix(gf, k, k + r, "cauchy")
        dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
        assert dev.route_for(G[k:]) == "panel"
        B, TW = 8, 8192
        words = rng.integers(
            0, 1 << 32, size=(B, k, TW), dtype=np.uint64
        ).astype(np.uint32)
        n_dev = router.n_dev_for(B)
        parity = router.matmul_words_batch(dev, G[k:], words)
        mode_calls = default_registry().counter(
            "noise_ec_mesh_sharded_dispatches_total"
        ).labels(mode="shard_map")
        assert mode_calls.value >= 1
        want0 = gf.matvec_stripes(
            G[k:], words[0].view(np.uint8).reshape(k, -1)
        )
        np.testing.assert_array_equal(
            np.asarray(parity)[0].view(np.uint8).reshape(r, -1), want0
        )
        data_dev = jax.device_put(words, router.sharding_for(n_dev))
        assemble = jax.jit(
            lambda d, p: jnp.concatenate([d, p], axis=1).at[:, 5, :].set(
                jnp.concatenate([d, p], axis=1)[:, 5, :]
                ^ np.uint32(0xA5A5A5A5)
            ),
            out_shardings=router.sharding_for(n_dev),
        )
        full = assemble(data_dev, parity)
        from noise_ec_tpu.matrix.linalg import gf_inv

        A = gf.matmul(
            G[k:].astype(np.int64), gf_inv(gf, G[:k]).astype(np.int64)
        ).astype(np.uint8)
        assert dev.route_for(dev.decode1_matrix(A, 5)) == "panel"
        reshard = default_registry().counter("noise_ec_mesh_reshard_total")
        reshard0 = reshard.labels().value
        corrected, bad = router.decode1_words_batch(dev, A, 5, full)
        assert reshard.labels().value == reshard0, (
            "chained panel encode→decode resharded"
        )
        assert not np.asarray(bad).any()
        np.testing.assert_array_equal(
            np.asarray(corrected), words[:, 5, :]
        )
    finally:
        reset_mesh_router()


# --------------------------------------------------- slow wide sweeps


@pytest.mark.slow
def test_panel_rs100_30_identity_slow(rng):
    """RS(100,30) (the bench sweep's mid point) through the forced
    panel words pipeline vs host truth — ~95k raw XORs, minutes of
    trace+compile on the interpret backend, so slow-marked."""
    from noise_ec_tpu.ops.dispatch import _panel_words_fn

    gf = GF256()
    k, r = 100, 30
    G = generator_matrix(gf, k, k + r, "cauchy")
    bits_rows = bits_to_rows(expand_generator_bits(gf, G[k:]))
    plan = panel_plan(bits_rows, 8 * k)
    TW = 8192
    words = rng.integers(
        0, 1 << 32, size=(k, TW), dtype=np.uint64
    ).astype(np.uint32)
    fn = _panel_words_fn(r, 8, bits_rows, plan, True)
    got = np.asarray(fn(jnp.asarray(words)))
    want = gf.matvec_stripes(G[k:], words.view(np.uint8).reshape(k, -1))
    np.testing.assert_array_equal(got.view(np.uint8).reshape(r, -1), want)


@pytest.mark.slow
def test_panel_rs200_56_identity_both_fields_slow(rng):
    """The widest sweep geometry, both fields, directly on the panel
    matmul kernel (the words pipelines add nothing network-wise):
    RS(200,56) gf256 (~361k raw XORs) byte-identical to the planes
    reference; the gf65536 equivalent at the same (448-row) network
    via its unpermuted byte-row expansion."""
    gf = GF256()
    k, r = 200, 56
    G = generator_matrix(gf, k, k + r, "cauchy")
    bits = expand_generator_bits(gf, G[k:])
    rows = bits_to_rows(bits)
    planes = rng.integers(0, 2**32, size=(8 * k, 160), dtype=np.uint32)
    want = gf2_matmul_planes(bits, planes)
    plan = panel_plan(rows, 8 * k)
    out = gf2_matmul_pallas_panel_rows(
        rows, planes_to_tiled(jnp.asarray(planes)), plan=plan,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(tiled_to_planes(out, 160)), want
    )
    # Wide field at the same scale: RS(100,30) gf65536 — its expanded
    # byte-row network is RS(200,56)-sized (480 x 1600 bits).
    gf16 = GF65536()
    G16 = generator_matrix(gf16, 100, 130, "cauchy")
    bits16 = expand_generator_bits(gf16, G16[100:])
    rows16 = bits_to_rows(bits16)
    plan16 = panel_plan(rows16, 16 * 100)
    planes16 = rng.integers(
        0, 2**32, size=(16 * 100, 160), dtype=np.uint32
    )
    want16 = gf2_matmul_planes(bits16, planes16)
    out16 = gf2_matmul_pallas_panel_rows(
        rows16, planes_to_tiled(jnp.asarray(planes16)), plan=plan16,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(tiled_to_planes(out16, 160)), want16
    )
