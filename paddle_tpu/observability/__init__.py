"""Observability: tracing, streaming SLO metrics, and a flight recorder.

Grown from the profiler stub in the spirit of XLA's xplane/TensorBoard
pipeline, in three layers (PR 2 + PR 12):

1. **Trace attribution** — ``span(name, **args)`` is a host span on the
   profiler's own clock for code that runs on the host every step (the
   serving engine's ``serve.*`` phases: a ``TraceAnnotation`` and nothing
   else, recorded while a profiler session runs); ``comm_span`` is for
   collective sites inside traced programs only (it names the site in
   the HLO metadata and tallies static counters at trace time); and
   ``RequestTracer`` gives every serving request a span tree (queue
   wait, prefill chunks, decode iterations, evictions) beside the
   engine's phases, exported as JSONL / Chrome trace JSON
   (``write_chrome_trace``, shared with the profiler) for Perfetto.
2. **Streaming metrics** — ``StepMetrics`` collects wall step time,
   compile time, tokens/sec, device memory and MFU with zero host syncs
   on the hot path; ``LogHistogram`` keeps fixed-memory TTFT/TPOT/
   queue-wait/step-time distributions with live percentiles, rendered by
   ``render_prometheus`` for scraping.
3. **Failure flight recorder** — ``FlightRecorder`` rings the last N
   iteration/step records and dumps them to ``PADDLE_TPU_TELEMETRY_DIR``
   on exception, eviction storm, or MAD step-time spike (one window per
   kind of step: a serving iteration with a prefill chunk, without).
4. **Fleet view** (PR 15) — ``MetricsRegistry`` is the single Prometheus
   exposition every surface registers into; ``FleetMonitor`` aggregates
   per-rank step times, per-``site=`` comm_span hop stats and all-device
   memory across ranks (one host-side allgather per interval), computes
   worst/median rank + straggler attribution + desync, and hooks
   non-finite-loss / grad-norm-spike / HBM-watermark anomalies into the
   shared flight-recorder ring.
5. **Performance attribution** (PR 17) — ``RooflineLedger`` itemizes
   step time into named kernel/component lines from the ``cost_estimate``
   FLOPs/bytes every pallas_call site declares, classifies each as
   compute- or memory-bound against the per-platform peak/HBM tables
   with an explicit unattributed remainder; ``merge_device_trace`` joins
   jax.profiler device events with host spans into one Perfetto view;
   ``regress`` ratchets bench rungs against ``PERF_BASELINE.json``.

Switched by ``PADDLE_TPU_TELEMETRY`` / ``PADDLE_TPU_TRACE_REQUESTS`` /
``PADDLE_TPU_FLIGHT_RECORDER`` / ``PADDLE_TPU_FLEET`` /
``PADDLE_TPU_LEDGER`` (+ ``PADDLE_TPU_TELEMETRY_DIR`` /
``PADDLE_TPU_LEDGER_DIR`` for file output).
"""
from .exporters import (JsonlWriter, TensorBoardWriter, get_logger,  # noqa: F401
                        load_jsonl, log_event, process_rank,
                        write_chrome_trace)
from .fleet import (FleetMonitor, device_memory_all,  # noqa: F401
                    fleet_enabled)
from .flight_recorder import (FlightRecorder, flight_recorder_enabled,  # noqa: F401
                              load_dump)
from .histogram import (LogHistogram, histogram_sample_lines,  # noqa: F401
                        render_prometheus)
from .ledger import (HBM_BW_TABLE, RooflineLedger,  # noqa: F401
                     flagship_component_specs, hbm_bw_per_device,
                     ledger_dir, ledger_enabled, load_device_trace_events,
                     merge_device_trace)
from .metrics import (PEAK_FLOPS_TABLE, StepMetrics, active,  # noqa: F401
                      peak_flops_info, peak_flops_per_device, set_active)
from .registry import MetricsRegistry  # noqa: F401
from .request_trace import RequestTracer  # noqa: F401
from .trace import (ENV_TELEMETRY, ENV_TELEMETRY_DIR, comm_span,  # noqa: F401
                    counters, overlap_flags, record_counter, reset_counters,
                    set_counter, span, telemetry_dir, telemetry_enabled)
