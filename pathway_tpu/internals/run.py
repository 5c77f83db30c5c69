"""pw.run() — execute every registered output sink.

Reference: python/pathway/internals/run.py:13.  Batch graphs execute to
completion; graphs with live sources run the streaming poll loop;
PATHWAY_THREADS>1 routes BOTH through the sharded data-plane
(parallel/sharded.py), which mirrors the streaming loop's async ticks and
elastic workload tracking.
"""

from __future__ import annotations

from typing import Any

from ..engine.runner import GraphRunner, has_live_sources
from . import parse_graph as pg


def run(
    *,
    debug: bool = False,
    monitoring_level: Any = None,
    with_http_server: bool = False,
    default_logging: bool = True,
    persistence_config: Any = None,
    runtime_typechecking: bool = False,
    terminate_on_error: bool = True,
    autocommit_duration_ms: int = 50,
    timeout_s: float | None = None,
    idle_stop_s: float | None = None,
    **kwargs: Any,
) -> None:
    sinks = list(pg.G.outputs)
    if not sinks:
        return
    from ..compile_cache import enable_compile_cache

    enable_compile_cache()
    from ..io._synchronization import apply_synchronization_groups

    apply_synchronization_groups()
    from ..engine.telemetry import global_error_log

    global_error_log.clear()
    from .config import pathway_config

    n_shards = max(1, pathway_config.threads)
    n_procs = max(1, pathway_config.processes)
    # worker cap without the unlimited-workers entitlement (reference:
    # MAX_WORKERS=8, dataflow/config.rs:11-15,149-151 — warn and reduce)
    MAX_WORKERS = 8
    if n_shards * n_procs > MAX_WORKERS:
        from .licensing import LicenseError, check_entitlements

        try:
            check_entitlements("unlimited-workers")
        except LicenseError:
            import logging

            log = logging.getLogger("pathway_tpu")
            new_shards = max(1, MAX_WORKERS // n_procs)
            if n_procs > MAX_WORKERS:
                # a single process cannot shrink the cluster it was spawned
                # into — the supervisor (cli.spawn) clamps processes; here
                # we can only floor threads and say so honestly
                log.warning(
                    "%d processes exceeds the %d-worker cap and cannot be "
                    "reduced from inside a worker; the spawn supervisor "
                    "clamps process counts — 'unlimited-workers' "
                    "entitlement required for this size",
                    n_procs, MAX_WORKERS,
                )
            if new_shards != n_shards:
                log.warning(
                    "%d workers exceeds the maximum allowed (%d) without "
                    "the 'unlimited-workers' entitlement; reducing threads "
                    "%d -> %d",
                    n_shards * n_procs, MAX_WORKERS, n_shards, new_shards,
                )
            n_shards = new_shards
    streaming = has_live_sources(sinks)

    from ..engine.telemetry import global_tracer

    # round-11: when an OTLP endpoint is configured, the flight
    # recorder's background flusher ships request/data-plane spans to it
    # for the run's lifetime (no-op otherwise; atexit stops it cleanly)
    from .. import obs as _obs

    _obs.maybe_start_flusher_from_env()

    _build_span = global_tracer.span("pathway.graph_build", sinks=len(sinks))
    _build_span.__enter__()
    try:
        # exactly one runner is built and instrumented
        if n_shards > 1 or n_procs > 1:
            from ..parallel.cluster import ClusterRunner

            runner: Any = ClusterRunner(
                sinks,
                n_local_shards=n_shards,
                pid=pathway_config.process_id,
                nprocs=n_procs,
                first_port=pathway_config.first_port,
            )
            if terminate_on_error:
                from ..engine import operators as _o

                for lg in runner.graphs.values():
                    for op in lg.scheduler.operators:
                        if isinstance(op, _o.OutputOperator):
                            op.terminate_on_error = True
            scheduler = runner.lg.scheduler  # first-owned-shard counters
        else:
            runner = GraphRunner(sinks, terminate_on_error=terminate_on_error)
            scheduler = runner.lg.scheduler

        if persistence_config is not None:
            from ..persistence import attach_persistence

            attach_persistence(runner, persistence_config)
    finally:
        _build_span.__exit__(None, None, None)

    metrics = reporter = dashboard = recorder = None
    if with_http_server:
        from ..engine.telemetry import MetricsServer

        metrics = MetricsServer(scheduler)
        metrics.fabric = getattr(runner, "fabric", None)
        metrics.start()
    import os as _os

    _metrics_dir = _os.environ.get("PATHWAY_DETAILED_METRICS_DIR")
    if _metrics_dir:
        # detailed-metrics recording for the web dashboard (reference:
        # web_dashboard/db.py reads metrics_*.db from this directory)
        from ..web_dashboard.db import MetricsRecorder

        recorder = MetricsRecorder(
            scheduler, _metrics_dir,
            worker_id=pathway_config.process_id,
            graph={
                "nodes": [
                    {"id": op.id, "name": op.name} for op in scheduler.operators
                ],
                "edges": [
                    [up.id, op.id]
                    for op in scheduler.operators
                    for up in op.inputs
                ],
            },
        )
        recorder.start()
    from ..internals.monitoring import MonitoringDashboard, MonitoringLevel

    if monitoring_level not in (None, MonitoringLevel.NONE):
        import sys as _sys

        if streaming and _sys.stderr.isatty():
            # live TUI for interactive streaming runs (reference:
            # internals/monitoring.py:56-249)
            dashboard = MonitoringDashboard(
                scheduler,
                monitoring_level
                if isinstance(monitoring_level, MonitoringLevel)
                else MonitoringLevel.IN_OUT,
            )
            dashboard.start()
        else:
            from ..engine.telemetry import ProgressReporter

            reporter = ProgressReporter(scheduler)
            reporter.start()
    try:
        with global_tracer.span(
            "pathway.run", streaming=streaming, shards=n_shards, procs=n_procs
        ):
            if streaming:
                runner.run_streaming(
                    autocommit_ms=autocommit_duration_ms,
                    timeout_s=timeout_s,
                    idle_stop_s=idle_stop_s,
                )
            else:
                runner.run_batch()
    finally:
        global_tracer.export()
        import os as _os

        _mon = _os.environ.get("PATHWAY_MONITORING_SERVER")
        if _mon:
            from ..engine.telemetry import otlp_export_metrics

            try:
                otlp_export_metrics(
                    _mon, scheduler, fabric=getattr(runner, "fabric", None)
                )
            except Exception:
                import logging

                logging.getLogger(__name__).warning(
                    "OTLP metrics export to %s failed", _mon, exc_info=True
                )
        if dashboard is not None:
            dashboard.stop()
        if reporter is not None:
            reporter.stop()
        if metrics is not None:
            metrics.stop()
        if recorder is not None:
            recorder.stop()
    if global_error_log.entries:
        first = global_error_log.entries[0]
        import logging

        logging.getLogger("pathway_tpu").warning(
            "%d expression error(s) during run; first: %s (%s)",
            len(global_error_log.entries), first["message"], first["operator"],
        )


def run_all(**kwargs: Any) -> None:
    run(**kwargs)
