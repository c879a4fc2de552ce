"""Seeded night-sky event generator: the benchmark's copy of the scenario
families in ``src/repro/data/synthetic.py``.

Kept here so that a later rewrite of the program's generator cannot move
the yardstick. The statistics are those of the program's scenario layer
as calibrated against EVAS night-sky recordings: uniform shot noise, a
scintillating star field with sidereal drift, and resident space objects
on linear, slow, tumbling or curved paths, optionally under platform
jitter. Ground-truth labels are dropped: the benchmark needs events only.

With one seed the random draws follow the original's order, so the events
equal ``make_scenario``'s. ``arrivals_seed`` splits them: every draw that
sets how many events come and when (rates, counts, times, tumbling, the
RSOs' paths) comes from it, and only where on the sensor they land (noise
and star positions, PSF scatter, polarity, jitter phase) from ``seed``. So
seeds that share the arrivals give the same work in another layout.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Lens configurations: focal scale multiplies apparent velocities and
# divides the star density.
LENS_CONFIGS = {
    "standard": dict(scale=1.0, n_stars=36),
    "telephoto": dict(scale=2.2, n_stars=14),
    "wide": dict(scale=0.55, n_stars=60),
}


@dataclasses.dataclass(frozen=True)
class RSOSpec:
    """One resident space object: (lo, hi) ranges sampled per recording;
    ``tumble_hz > 0`` modulates the event rate sinusoidally."""

    speed_px_s: tuple[float, float] = (40.0, 150.0)
    accel_px_s2: tuple[float, float] = (0.0, 0.0)
    rate_hz: tuple[float, float] = (380.0, 700.0)
    tumble_hz: float = 0.0
    tumble_depth: float = 0.9


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    rsos: tuple[RSOSpec, ...] = ()
    lens: str = "standard"
    noise_rate_hz: float = 3_500.0
    star_rate_hz: tuple[float, float] = (15.0, 60.0)
    hot_columns: int = 0
    hot_pixels_per_column: int = 24
    hot_pixel_rate_hz: float = 800.0
    n_bursts: int = 0
    burst_rate_hz: float = 60_000.0
    burst_ms: float = 30.0
    jitter_px: float = 0.0
    jitter_hz: float = 4.0
    duration_s: float = 2.0


FAMILIES: dict[str, Scenario] = {
    "crossing": Scenario(name="crossing", rsos=(RSOSpec(), RSOSpec())),
    "geo_slow": Scenario(
        name="geo_slow",
        rsos=(
            RSOSpec(speed_px_s=(0.5, 3.0), rate_hz=(420.0, 650.0)),
            RSOSpec(speed_px_s=(1.0, 5.0), rate_hz=(420.0, 650.0)),
        ),
    ),
    "tumbling": Scenario(
        name="tumbling",
        rsos=(
            RSOSpec(tumble_hz=5.0, rate_hz=(500.0, 800.0)),
            RSOSpec(tumble_hz=2.5, tumble_depth=1.0, rate_hz=(500.0, 800.0)),
        ),
    ),
    "ballistic": Scenario(
        name="ballistic",
        rsos=(
            RSOSpec(speed_px_s=(30.0, 90.0), accel_px_s2=(40.0, 120.0)),
            RSOSpec(speed_px_s=(40.0, 110.0), accel_px_s2=(30.0, 90.0)),
        ),
    ),
    "hot_columns": Scenario(name="hot_columns", rsos=(RSOSpec(),), hot_columns=3),
    "noise_burst": Scenario(name="noise_burst", rsos=(RSOSpec(),), n_bursts=5),
    "jitter": Scenario(
        name="jitter", rsos=(RSOSpec(), RSOSpec()), jitter_px=2.5, jitter_hz=6.0
    ),
}


def _poisson_times(rng: np.random.Generator, rate_hz: float, duration_us: int) -> np.ndarray:
    n = rng.poisson(rate_hz * duration_us * 1e-6)
    return np.sort(rng.uniform(0, duration_us, size=n)).astype(np.int64)


def _tumble_thin(rng: np.random.Generator, t_us: np.ndarray, spec: RSOSpec) -> np.ndarray:
    if spec.tumble_hz <= 0.0 or len(t_us) == 0:
        return np.ones(len(t_us), bool)
    phase = rng.uniform(0, 2 * np.pi)
    ts = t_us * 1e-6
    m = (1.0 - spec.tumble_depth) + spec.tumble_depth * 0.5 * (
        1.0 + np.sin(2 * np.pi * spec.tumble_hz * ts + phase)
    )
    return rng.uniform(size=len(t_us)) < m


def make_events(
    scenario: Scenario,
    seed,
    width: int,
    height: int,
    psf_sigma: float = 0.8,
    arrivals_seed=None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Time-sorted ``(x, y, t_us, p)`` int64 events of one scenario."""
    rng = np.random.default_rng(seed)
    ra = rng if arrivals_seed is None else np.random.default_rng(arrivals_seed)
    cfg = LENS_CONFIGS[scenario.lens]
    scale = cfg["scale"]
    n_stars = cfg["n_stars"]
    duration_s = scenario.duration_s
    duration_us = int(duration_s * 1e6)
    xs, ys, ts, ps = [], [], [], []

    def add(x, y, t):
        xs.append(np.asarray(x, np.float64))
        ys.append(np.asarray(y, np.float64))
        ts.append(np.asarray(t, np.int64))
        ps.append(rng.integers(0, 2, len(t)))

    t_noise = _poisson_times(ra, scenario.noise_rate_hz, duration_us)
    n = len(t_noise)
    add(rng.integers(0, width, n), rng.integers(0, height, n), t_noise)

    for _ in range(scenario.n_bursts):
        b_us = int(scenario.burst_ms * 1e3)
        t0 = int(ra.uniform(0, max(duration_us - b_us, 1)))
        t_b = _poisson_times(ra, scenario.burst_rate_hz, b_us) + t0
        n = len(t_b)
        add(rng.integers(0, width, n), rng.integers(0, height, n), t_b)

    for _ in range(scenario.hot_columns):
        col = int(rng.integers(0, width))
        rows = rng.choice(height, size=scenario.hot_pixels_per_column, replace=False)
        for r in rows:
            t_h = _poisson_times(ra, scenario.hot_pixel_rate_hz, duration_us)
            add(np.full(len(t_h), col), np.full(len(t_h), r), t_h)

    star_x = rng.uniform(30, width - 30, n_stars)
    star_y = rng.uniform(30, height - 30, n_stars)
    drift = rng.normal(0.0, 0.6, (n_stars, 2)) * scale
    for s in range(n_stars):
        rate = ra.uniform(*scenario.star_rate_hz)
        t_s = _poisson_times(ra, rate, duration_us)
        n = len(t_s)
        if n == 0:
            continue
        tt = t_s * 1e-6
        add(
            star_x[s] + drift[s, 0] * tt + rng.normal(0, psf_sigma, n),
            star_y[s] + drift[s, 1] * tt + rng.normal(0, psf_sigma, n),
            t_s,
        )

    for spec in scenario.rsos:
        speed = ra.uniform(*spec.speed_px_s) * scale
        angle = ra.uniform(0, 2 * np.pi)
        vx, vy = speed * np.cos(angle), speed * np.sin(angle)
        a_mag = ra.uniform(*spec.accel_px_s2) * scale
        a_angle = ra.uniform(0, 2 * np.pi)
        ax, ay = a_mag * np.cos(a_angle), a_mag * np.sin(a_angle)
        half = duration_s / 2
        x0 = ra.uniform(0.25 * width, 0.75 * width) - vx * half - 0.5 * ax * half * half
        y0 = ra.uniform(0.25 * height, 0.75 * height) - vy * half - 0.5 * ay * half * half
        rate = ra.uniform(*spec.rate_hz)
        t_r = _poisson_times(ra, rate, duration_us)
        t_r = t_r[_tumble_thin(ra, t_r, spec)]
        n = len(t_r)
        tt = t_r * 1e-6
        px = x0 + vx * tt + 0.5 * ax * tt * tt + rng.normal(0, psf_sigma, n)
        py = y0 + vy * tt + 0.5 * ay * tt * tt + rng.normal(0, psf_sigma, n)
        inside = (px >= 0) & (px < width) & (py >= 0) & (py < height)
        add(px[inside], py[inside], t_r[inside])

    x = np.concatenate(xs)
    y = np.concatenate(ys)
    t = np.concatenate(ts).astype(np.int64)
    p = np.concatenate(ps).astype(np.int64)
    if scenario.jitter_px > 0.0:
        phx, phy = rng.uniform(0, 2 * np.pi, 2)
        w = 2 * np.pi * scenario.jitter_hz
        tt = t * 1e-6
        x = x + scenario.jitter_px * np.sin(w * tt + phx)
        y = y + scenario.jitter_px * np.sin(w * tt + phy)
    x = np.clip(x, 0, width - 1).astype(np.int64)
    y = np.clip(y, 0, height - 1).astype(np.int64)
    order = np.argsort(t, kind="stable")
    return x[order], y[order], t[order], p[order]
