"""Fixed-point datapath vs float golden model vs fused megakernel (ISSUE 6).

Three-way differential layer over the per-window stage chain:

* float golden vs staged fixed (``numerics="fixed"``): pins the exact
  claims of DESIGN.md Sec. 12 — bit-identical conditioning, cluster
  counts/cells/validity, patch origins, and the shannon/renyi/
  local-contrast/event-count metrics; bounded centroid quantization
  (<= 2**-8 px) and bounded differential-entropy / edge-density shifts;
* staged fixed vs fused Pallas megakernel: bit-identical on EVERY
  surface (cluster fields, all six metrics, tracker state) — the shared
  float epilogue makes this structural, these tests keep it true;
* primitive helpers (round_div_half_even, isqrt) vs exact oracles.

Windows cover randomized clustered scenes plus the adversarial shapes:
empty, single-event, all-same-pixel (hot filter), capacity-saturated,
out-of-bounds coordinates, and ROI-boundary straddlers.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or deterministic fallback

from repro.core import metrics as M
from repro.core.events import batch_from_arrays
from repro.core.fixed_point import (
    CENTROID_ONE,
    fixed_window_stage,
    isqrt,
    make_fixed_process_window,
    round_div_half_even,
)
from repro.core.pipeline import (
    PipelineConfig,
    init_tracks,
    make_process_window,
    run_recording_scan,
)
from repro.data.synthetic import make_recording
from repro.kernels import ops as kops
from repro.kernels import ref as kref

CONFIG = PipelineConfig()
FIXED = dataclasses.replace(CONFIG, numerics="fixed")
MEGA = dataclasses.replace(CONFIG, numerics="fixed", metrics_impl="megakernel")

# Exact-claim metrics (identical integers -> identical float expressions)
# vs bounded-claim metrics (DESIGN.md Sec. 12 bounds).
EXACT_METRICS = ("shannon_entropy", "renyi_entropy", "local_contrast", "event_count")
CENTROID_TOL = 2.0**-8  # UQ10.8 quantization
DIFF_ENTROPY_TOL = 0.05  # integer floor-sqrt first moment (measured ~0.024)
EDGE_DENSITY_TOL = 8.0 / (M.WINDOW * M.WINDOW)  # threshold-straddling pixels


def _random_batch(seed, n=160, capacity=128):
    rng = np.random.default_rng(seed)
    centers = rng.integers(40, 580, (4, 2))
    pick = rng.integers(0, 4, n)
    x = np.clip(centers[pick, 0] + rng.integers(-12, 13, n), 0, 639)
    y = np.clip(centers[pick, 1] % 440 + rng.integers(-12, 13, n), 0, 479)
    t = np.sort(rng.integers(0, 20_000, n))
    batch = batch_from_arrays(x, y, t, rng.integers(0, 2, n), capacity)
    valid = np.asarray(batch.valid) & (rng.random(capacity) > 0.1)
    return batch._replace(valid=jnp.asarray(valid))


def _adversarial_batches(capacity=128):
    """Named edge-shape windows for the differential sweep."""
    rng = np.random.default_rng(0xF1)
    out = {}

    empty = _random_batch(1, capacity=capacity)
    out["empty"] = empty._replace(valid=jnp.zeros_like(empty.valid))

    out["single_event"] = batch_from_arrays(
        np.array([300]), np.array([200]), np.array([5]), np.array([1]), capacity
    )

    # Every event on one pixel: the hot-pixel filter must kill the lot.
    n = 40
    out["all_same_pixel"] = batch_from_arrays(
        np.full(n, 321), np.full(n, 234), np.arange(n), np.zeros(n), capacity
    )

    # Saturated: every slot valid, clustered tight (coincidences > 1).
    x = 100 + rng.integers(0, 25, capacity)
    y = 100 + rng.integers(0, 25, capacity)
    out["capacity_saturated"] = batch_from_arrays(
        x, y, np.sort(rng.integers(0, 9_000, capacity)), np.zeros(capacity), capacity
    )

    # Out-of-bounds coordinates mixed with a real cluster: must be
    # masked, never wrapped onto another cell/patch row.
    x = np.concatenate([640 + rng.integers(0, 50, 30), 200 + rng.integers(0, 10, 50)])
    y = np.concatenate([rng.integers(500, 600, 30), 300 + rng.integers(0, 10, 50)])
    out["out_of_bounds"] = batch_from_arrays(
        x, y, np.arange(80), np.zeros(80), capacity
    )

    # Straddling the ROI edge (x0=20): half the cluster is cut away.
    x = 14 + rng.integers(0, 12, 90)
    y = 200 + rng.integers(0, 12, 90)
    out["roi_boundary"] = batch_from_arrays(
        x, y, np.arange(90), np.zeros(90), capacity
    )
    return out


def _stack(batches):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)


# ---------------------------------------------------------------------------
# Primitive oracles.
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_round_div_half_even_matches_float_round(seed):
    rng = np.random.default_rng(seed)
    num = rng.integers(0, 2**26, 256)
    den = rng.integers(1, 257, 256)
    got = round_div_half_even(
        jnp.asarray(num, jnp.int32), jnp.asarray(den, jnp.int32)
    )
    # Host-side float64 oracle: the quotient is < 2**26 so the division
    # is correctly rounded and .5 boundaries are representable — np.round
    # is exact round-half-even here.
    want = np.round(num.astype(np.float64) / den.astype(np.float64))
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int64))


def test_round_div_half_even_ties_to_even():
    # Exact .5 boundaries round to the even quotient, like jnp.round.
    num = jnp.asarray([1, 3, 5, 7, 250 * 2 + 1], jnp.int32)
    den = jnp.asarray([2, 2, 2, 2, 2], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(round_div_half_even(num, den)), [0, 2, 2, 4, 250]
    )


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_isqrt_matches_math_isqrt(seed):
    rng = np.random.default_rng(seed)
    v = rng.integers(0, 2**26, 256)
    got = np.asarray(isqrt(jnp.asarray(v, jnp.int32)))
    want = np.array([math.isqrt(int(u)) for u in v])
    np.testing.assert_array_equal(got, want)


def test_isqrt_perfect_square_edges():
    v = jnp.asarray([0, 1, 2, 3, 4, 255, 256, 257, 2**26 - 1], jnp.int32)
    want = [math.isqrt(int(u)) for u in np.asarray(v)]
    np.testing.assert_array_equal(np.asarray(isqrt(v)), want)


# ---------------------------------------------------------------------------
# Config validation.
# ---------------------------------------------------------------------------

def test_fixed_config_rejects_float_only_knobs():
    for bad in (
        dataclasses.replace(FIXED, merge_neighbors=True),
        dataclasses.replace(FIXED, use_kernels=True),
        dataclasses.replace(FIXED, metrics_impl="frame"),
        dataclasses.replace(FIXED, metrics_impl="kernel"),
    ):
        with pytest.raises(ValueError):
            make_fixed_process_window(bad)
    with pytest.raises(ValueError):
        make_process_window(dataclasses.replace(CONFIG, numerics="fp8"))


# ---------------------------------------------------------------------------
# Float golden vs staged fixed: the Sec. 12 claims.
# ---------------------------------------------------------------------------

def _assert_fixed_matches_float(batch):
    clusters_f, mets_f = make_process_window(CONFIG)(batch)
    clusters_x, mets_x = make_process_window(FIXED)(batch)

    # Bit-identical cluster structure.
    for field in ("count", "cell_x", "cell_y", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(clusters_x, field)),
            np.asarray(getattr(clusters_f, field)),
            err_msg=field,
        )
    # Centroids: Q10.8 quantization bound (invalid slots share -1.0).
    for field in ("centroid_x", "centroid_y", "centroid_t"):
        np.testing.assert_allclose(
            np.asarray(getattr(clusters_x, field)),
            np.asarray(getattr(clusters_f, field)),
            atol=CENTROID_TOL, rtol=0, err_msg=field,
        )
    # Patch origins: exact integer division == round(float centroid).
    fc, _ = jax.jit(lambda b: fixed_window_stage(FIXED, b))(batch)
    gx0, gy0 = M.window_origin(
        clusters_f.centroid_x, clusters_f.centroid_y,
        CONFIG.grid.width, CONFIG.grid.height, M.WINDOW,
    )
    valid = np.asarray(clusters_f.valid)
    np.testing.assert_array_equal(np.asarray(fc.x0)[valid], np.asarray(gx0)[valid])
    np.testing.assert_array_equal(np.asarray(fc.y0)[valid], np.asarray(gy0)[valid])

    for name in EXACT_METRICS:
        np.testing.assert_array_equal(
            np.asarray(mets_x[name]), np.asarray(mets_f[name]), err_msg=name
        )
    np.testing.assert_allclose(
        np.asarray(mets_x["edge_density"]), np.asarray(mets_f["edge_density"]),
        atol=EDGE_DENSITY_TOL, rtol=0,
    )
    np.testing.assert_allclose(
        np.asarray(mets_x["differential_entropy"]),
        np.asarray(mets_f["differential_entropy"]),
        atol=DIFF_ENTROPY_TOL, rtol=0,
    )


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_fixed_matches_float_random_windows(seed):
    _assert_fixed_matches_float(_random_batch(seed))


@pytest.mark.parametrize("name", sorted(_adversarial_batches()))
def test_fixed_matches_float_adversarial(name):
    _assert_fixed_matches_float(_adversarial_batches()[name])


def test_all_same_pixel_yields_no_clusters():
    # The hot-pixel filter must kill a 40-repeat pixel in BOTH numerics.
    batch = _adversarial_batches()["all_same_pixel"]
    for config in (CONFIG, FIXED, MEGA):
        clusters, mets = make_process_window(config)(batch)
        assert not np.asarray(clusters.valid).any(), config.numerics
        assert np.asarray(mets["event_count"]).sum() == 0.0


# ---------------------------------------------------------------------------
# Staged fixed vs fused megakernel: total bit-identity.
# ---------------------------------------------------------------------------

def _assert_mega_matches_staged(stacked):
    fc_k, mets_k = jax.jit(
        lambda s: kops.window_pipeline_call(s, MEGA)
    )(stacked)
    fc_r, mets_r = jax.jit(
        lambda s: kref.window_pipeline_ref(s, FIXED)
    )(stacked)
    for field in fc_k._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fc_k, field)),
            np.asarray(getattr(fc_r, field)),
            err_msg=field,
        )
    for name in M.METRIC_NAMES:
        got = np.asarray(mets_k[name]).view(np.int32)
        want = np.asarray(mets_r[name]).view(np.int32)
        np.testing.assert_array_equal(got, want, err_msg=name)
    return fc_k


def _cells_batch(cells, capacity=256):
    """One window of ``n`` events in distinct pixels of each 16-px grid
    cell ``(cx, cy, n)``, in list order (all inside the default ROI)."""
    xs, ys = [np.zeros(0, int)], [np.zeros(0, int)]
    for cx, cy, n in cells:
        i = np.arange(n)
        xs.append(cx * 16 + i % 16)
        ys.append(cy * 16 + i // 16)
    x, y = np.concatenate(xs), np.concatenate(ys)
    return batch_from_arrays(x, y, np.arange(len(x)), np.zeros(len(x)), capacity)


def _prefix_boundary_cells():
    """name -> (cells, valid clusters): the megakernel runs its K-slot
    loops only over the valid prefix, so pin the prefix's edges."""
    k = CONFIG.grid.max_clusters
    me = CONFIG.grid.min_events
    grid = [(cx, cy) for cy in range(2, 25, 2) for cx in range(2, 35, 2)]
    return {
        # K valid cells (counts me..me+2), the next ones one short.
        "exactly_k_valid": (
            [(cx, cy, me + i % 3) for i, (cx, cy) in enumerate(grid[:k])]
            + [(cx, cy, me - 1) for cx, cy in grid[k:k + 16]],
            k,
        ),
        # More valid cells than slots, all tied: min(K, ...) clips, and
        # the slots take the lowest cell indices.
        "over_k_valid": ([(cx, cy, me + 1) for cx, cy in grid[:k + 8]], k),
        # A cell at exactly min_events beside one at min_events - 1.
        "threshold_neighbours": (
            [(10, 10, me), (11, 10, me - 1), (20, 12, me + 4), (21, 12, 2)],
            2,
        ),
        # Valid cells tied in count, listed from the highest cell index
        # down: slots follow first-index order, not arrival order.
        "tied_valid_counts": (
            [(cx, cy, me + 2) for cx, cy in reversed(grid[:6])]
            + [(cx, cy, me + 4) for cx, cy in reversed(grid[40:43])]
            + [(30, 20, 1)],
            9,
        ),
        "all_padding": ([], 0),
    }


@pytest.mark.parametrize("name", sorted(_prefix_boundary_cells()))
def test_megakernel_bit_identical_at_valid_prefix_edges(name):
    cells, n_valid = _prefix_boundary_cells()[name]
    fc = _assert_mega_matches_staged(_stack([_cells_batch(cells)]))
    valid = np.asarray(fc.valid)[0]
    assert valid.sum() == n_valid
    assert valid[:n_valid].all()  # the valid slots are a prefix


def test_megakernel_bit_identical_random_windows():
    _assert_mega_matches_staged(_stack([_random_batch(s) for s in range(3)]))


def test_megakernel_bit_identical_adversarial_windows():
    _assert_mega_matches_staged(_stack(list(_adversarial_batches().values())))


def test_megakernel_process_window_matches_staged():
    batch = _random_batch(11)
    cl_s, mets_s = make_process_window(FIXED)(batch)
    cl_m, mets_m = make_process_window(MEGA)(batch)
    for field in cl_s._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(cl_m, field)), np.asarray(getattr(cl_s, field))
        )
    for name in M.METRIC_NAMES:
        np.testing.assert_array_equal(
            np.asarray(mets_m[name]).view(np.int32),
            np.asarray(mets_s[name]).view(np.int32),
            err_msg=name,
        )


# ---------------------------------------------------------------------------
# Scan drivers: whole-recording differential, tracker included.
# ---------------------------------------------------------------------------

def test_fixed_scan_matches_float_scan_bounds():
    rec = make_recording(seed=3, duration_s=0.3)
    res_f = run_recording_scan(rec, CONFIG)
    res_x = run_recording_scan(rec, FIXED)
    np.testing.assert_array_equal(
        np.asarray(res_x.clusters.valid), np.asarray(res_f.clusters.valid)
    )
    np.testing.assert_array_equal(
        np.asarray(res_x.clusters.count), np.asarray(res_f.clusters.count)
    )
    for name in EXACT_METRICS:
        np.testing.assert_array_equal(
            np.asarray(res_x.metrics[name]), np.asarray(res_f.metrics[name]),
            err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(res_x.clusters.centroid_x),
        np.asarray(res_f.clusters.centroid_x),
        atol=CENTROID_TOL, rtol=0,
    )
    np.testing.assert_allclose(
        np.asarray(res_x.metrics["differential_entropy"]),
        np.asarray(res_f.metrics["differential_entropy"]),
        atol=DIFF_ENTROPY_TOL, rtol=0,
    )


def test_mega_scan_bit_identical_to_staged_scan():
    rec = make_recording(seed=3, duration_s=0.2)
    res_s = run_recording_scan(rec, FIXED)
    res_m = run_recording_scan(rec, MEGA)
    for field in res_s.clusters._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_m.clusters, field)),
            np.asarray(getattr(res_s.clusters, field)),
            err_msg=field,
        )
    for name in M.METRIC_NAMES:
        np.testing.assert_array_equal(
            np.asarray(res_m.metrics[name]).view(np.int32),
            np.asarray(res_s.metrics[name]).view(np.int32),
            err_msg=name,
        )
    # Tracker consumed identical inputs -> identical final state.
    for leaf_m, leaf_s in zip(
        jax.tree_util.tree_leaves(res_m.final_tracks),
        jax.tree_util.tree_leaves(res_s.final_tracks),
    ):
        np.testing.assert_array_equal(np.asarray(leaf_m), np.asarray(leaf_s))
