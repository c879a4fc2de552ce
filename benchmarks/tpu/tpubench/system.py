"""The system under test, built from a configuration file: the program's
``DetectionService`` with the file's pipeline and service settings.

This is the only module of the benchmark that imports the program, apart
from the fault hooks of the tests.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class System:
    config: object  # repro PipelineConfig
    service_kwargs: dict
    with_tracking: bool

    def service(self):
        from repro.serve import AdmissionConfig, DetectionService

        # Rounds fire only on pump(force=True): one round per beat of the
        # traffic, so the compiled shapes follow the traffic alone.
        admission = AdmissionConfig(max_delay_s=float("inf"), max_items=1 << 62)
        return DetectionService(self.config, admission=admission, **self.service_kwargs)

    def compile_counts(self) -> dict[str, int]:
        """Compiled programs held by the fleet step and the wire decoder."""
        from repro.core.pipeline import fleet

        step = fleet.make_fleet_fn(self.config, self.with_tracking)
        wire = fleet.make_wire_fn(
            self.config.batcher.capacity, self.config.use_kernels
        )
        return {"step": step._cache_size(), "decode": wire._cache_size()}

    def program_names(self) -> dict[str, str]:
        """Names the device trace gives the step's and decoder's programs."""
        from repro.core.pipeline import fleet

        step = fleet.make_fleet_fn(self.config, self.with_tracking)
        wire = fleet.make_wire_fn(
            self.config.batcher.capacity, self.config.use_kernels
        )
        return {"step": f"jit_{step.__name__}", "decode": f"jit_{wire.__name__}"}


def build(cfg: dict) -> System:
    """The program configured as ``cfg`` states; refuses a file whose
    constants the program cannot take."""
    from repro.core import metrics as M
    from repro.core.events import BatcherConfig
    from repro.core.grid_clustering import GridConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.tracking import TrackerConfig

    met = cfg["metrics"]
    if (M.WINDOW, M.HIST_BINS, M.EDGE_THRESHOLD) != (
        met["patch"], met["bins"], met["edge_threshold"]
    ):
        raise ValueError(
            f"{cfg['name']}: the program's metric patch, bins and edge threshold "
            f"are {(M.WINDOW, M.HIST_BINS, M.EDGE_THRESHOLD)}, the file states {met}"
        )
    if cfg["numerics"] == "fixed":
        from repro.core import fixed_point

        if fixed_point.CENTROID_FRAC != cfg["centroid_frac"]:
            raise ValueError(f"{cfg['name']}: centroid_frac differs from the program's")
    grid = GridConfig(
        width=cfg["sensor"]["width"], height=cfg["sensor"]["height"],
        cell_size=cfg["grid"]["cell_size"], min_events=cfg["grid"]["min_events"],
        max_clusters=cfg["grid"]["max_clusters"],
    )
    config = PipelineConfig(
        grid=grid,
        batcher=BatcherConfig(**cfg["batcher"]),
        tracker=TrackerConfig(**cfg["tracker"]),
        roi=tuple(cfg["roi"]),
        hot_pixel_max=cfg["hot_pixel_max"],
        numerics=cfg["numerics"],
        metrics_impl=cfg["metrics_impl"],
    )
    svc = cfg["service"]
    return System(
        config=config,
        service_kwargs=dict(
            tiers=tuple(svc["tiers"]),
            max_inflight_rounds=svc["max_inflight_rounds"],
            wire=svc["wire"],
            with_tracking=cfg["with_tracking"],
        ),
        with_tracking=cfg["with_tracking"],
    )
