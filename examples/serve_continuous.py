"""Serve a Llama with continuous batching over a paged KV cache.

Requests arrive on a Poisson trace with mixed prompt lengths; the engine
admits them against its free-block budget, interleaves chunked prefill
with bucketed decode batches (one compiled step family, recompiles
bounded and counted), and preempts-by-eviction if the block pool runs
dry. Tiny model on CPU (pallas interpret); the same engine drives the
flagship config on TPU (see bench.py serve_continuous).

The run also demos the observability stack: request-lifecycle tracing
(exported as a Chrome/Perfetto trace plus JSONL spans), the streaming
SLO histograms behind a Prometheus text snapshot, and the failure
flight recorder (clean shutdown here, so nothing is dumped).

Two robustness acts follow. First an overload burst against a
deliberately under-provisioned engine: the bounded queue and the
block-overcommit cap reject at submit() with a cause, deadline shedding
reclaims queued work that can no longer meet its TTFT budget, and the
outcomes() audit shows every request terminal — finished, rejected,
shed, or failed, never silently dropped. Then a crash: an engine
journaling to disk is abandoned mid-decode, and a fresh engine rebuilds
the schedule from the journal (recover()) and finishes every stream
bit-identically to an uninterrupted run — greedy decoding is
deterministic in (prompt + history), so tokens lost with the dead
engine's buffer are simply re-derived.

A final act shows prefix caching: requests sharing a long system prompt
hit the COW-shared block index, skip the shared span's prefill, and
still produce bitwise the tokens a cache-off engine produces.
"""
import os
import sys
import tempfile

import numpy as np

# runnable from the repo root without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params, llama_tiny
    from paddle_tpu.observability.metrics import StepMetrics

    config = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                        seq=256)
    params = init_llama_params(config, seed=0)
    serve = ServeConfig(block_size=128, num_blocks=17, max_batch=4,
                        prefill_chunk=64, max_seq_len=256)
    metrics = StepMetrics(name="serve", n_devices=1)
    engine = InferenceEngine(params, config, serve, telemetry=metrics,
                             trace_requests=True, flight_recorder=True)

    rng = np.random.RandomState(0)
    arrivals = np.cumsum(rng.exponential(1.0 / 8.0, size=6))  # Poisson 8/s
    lengths = rng.choice([8, 24, 96, 130], size=6)
    requests = [
        Request(rng.randint(1, config.vocab_size, size=int(n)).tolist(),
                max_new_tokens=8, arrival=float(t))
        for n, t in zip(lengths, arrivals)
    ]
    stats = engine.run(requests)

    print(f"served {stats['requests']} requests, "
          f"{stats['generated_tokens']} tokens "
          f"in {stats['iterations']} iterations")
    print(f"throughput: {stats['tokens_per_sec']:.1f} tok/s  "
          f"ttft p50/p99: {stats['ttft_p50_s']:.3f}/"
          f"{stats['ttft_p99_s']:.3f} s  "
          f"tpot p50/p99: {stats['tpot_p50_s']:.3f}/"
          f"{stats['tpot_p99_s']:.3f} s")
    print(f"compiled shapes: {sorted(stats['compiles'])}  "
          f"preemptions: {stats['preemptions']}  "
          f"pool leak-free: {engine.pool.used_blocks == 0}")
    for seq in sorted(engine.finished, key=lambda s: s.req.request_id):
        print(f"request {seq.req.request_id}: prompt {seq.n_prompt} tokens"
              f" -> continuation: {seq.generated}")

    # observability exports: open the chrome trace in Perfetto
    # (ui.perfetto.dev) — one row per top-level engine phase (serve.step,
    # .admit, .prefill, .decode, .report, .submit; plan / launch / wait /
    # commit nest on their parent's row), one row per request. Under
    # jax.profiler.start_trace the same spans land in the device trace.
    out = tempfile.mkdtemp(prefix="paddle_tpu_serve_")
    trace = engine.tracer.export_chrome(os.path.join(out, "serve_trace.json"))
    spans = engine.tracer.export_jsonl(os.path.join(out, "serve_spans.jsonl"))
    print(f"request trace: {engine.tracer.span_count()} spans -> {trace} "
          f"(Perfetto) and {spans} (JSONL)")
    print(f"streaming SLO estimates (fixed-memory histograms): "
          f"ttft p50 {stats['ttft_stream_p50_s']:.3f} s, "
          f"tpot p50 {stats['tpot_stream_p50_s']:.3f} s")
    prom = engine.render_prometheus()
    print(f"prometheus snapshot: {len(prom.splitlines())} lines, e.g.")
    for line in prom.splitlines():
        if line.startswith("# TYPE paddle_tpu_serve_ttft"):
            print(f"  {line}")
    print(f"flight recorder: ring {len(engine.recorder.ring)} records, "
          f"dumped: {engine.recorder.dumped or 'nothing (clean run)'}")

    # ---- act 2: overload burst against an under-provisioned engine ----
    # 8 requests into a 2-deep queue over a 4-block pool, with TTFT
    # deadlines the tail of the burst cannot meet: admission rejects
    # with a cause, the scheduler sheds expired queued work, and the
    # outcomes() audit accounts for every request. Deterministic mode:
    # arrivals/deadlines are iteration counts, so the shed set is
    # replayable bit-for-bit.
    over = ServeConfig(block_size=128, num_blocks=4, max_batch=1,
                       prefill_chunk=64, max_seq_len=256,
                       max_queue=2, overcommit=4.0)
    eng2 = InferenceEngine(params, config, over)
    burst = [Request(rng.randint(1, config.vocab_size, size=24).tolist(),
                     max_new_tokens=6, request_id=i, arrival=float(i),
                     ttft_deadline=8.0, deadline=30.0)
             for i in range(8)]
    st2 = eng2.run(burst, deterministic=True)
    audit = eng2.outcomes()
    terminal = {"finished", "rejected", "shed", "failed"}
    print(f"overload burst: {len(burst)} submitted -> "
          f"{st2['requests']} finished, {st2['rejected']} rejected, "
          f"{st2['shed']} shed, {st2['failed']} failed")
    for rid in sorted(audit):
        state, cause = audit[rid]
        print(f"  request {rid}: {state}"
              + (f" ({cause})" if cause else ""))
    print(f"no silent drops: "
          f"{all(s in terminal for s, _ in audit.values())}  "
          f"overload pool leak-free: {eng2.pool.used_blocks == 0}")

    # ---- act 3: crash mid-decode, recover from the engine journal ----
    jpath = os.path.join(out, "engine.jsonl")
    victim = InferenceEngine(params, config, serve, journal=jpath)
    work = [Request(rng.randint(1, config.vocab_size, size=n).tolist(),
                    max_new_tokens=8, request_id=i, arrival=0.0)
            for i, n in enumerate((12, 40, 72))]
    for r in work:
        victim.submit(r)
    for _ in range(4):          # a few iterations of real progress...
        victim.step()
    del victim                  # ...then the "crash": buffered tokens die
    successor = InferenceEngine(params, config, serve, journal=jpath)
    rec = successor.recover()
    successor.run([], deterministic=True)
    reference = InferenceEngine(params, config, serve)
    reference.run([Request(list(r.prompt), max_new_tokens=8,
                           request_id=r.request_id, arrival=0.0)
                   for r in work], deterministic=True)
    streams = lambda e: {s.req.request_id: list(s.generated)
                         for s in e.finished}
    print(f"journal recovery: replayed {rec['replayed']} requests "
          f"({rec['torn_lines']} torn lines) from {jpath}")
    print(f"recovered streams bit-identical to uninterrupted run: "
          f"{streams(successor) == streams(reference)}  "
          f"recovery pool leak-free: {successor.pool.used_blocks == 0}")

    # ---- act 4: prefix reuse — COW-shared KV blocks (PR 16) ----
    # Five requests share a 128-token "system prompt": the first prefill
    # registers its full block in the prefix index; every later request
    # matches it, acquires the block copy-on-write (no bytes copied —
    # writes land past the shared span by construction), and skips that
    # prefill work. Greedy tokens stay bitwise identical to a cache-off
    # run of the same trace; when the last reference drops the block
    # PARKS for future hits instead of freeing, so the leak audit still
    # reads zero used blocks.
    system = rng.randint(1, config.vocab_size, size=128).tolist()
    reuse = [Request(system + rng.randint(1, config.vocab_size,
                                          size=12).tolist(),
                     max_new_tokens=6, request_id=i, arrival=float(4 * i))
             for i in range(5)]
    cold = InferenceEngine(params, config, serve)
    cold.run([Request(list(r.prompt), max_new_tokens=6,
                      request_id=r.request_id, arrival=r.arrival)
              for r in reuse], deterministic=True)
    warm = InferenceEngine(
        params, config,
        ServeConfig(block_size=128, num_blocks=17, max_batch=4,
                    prefill_chunk=64, max_seq_len=256, prefix_cache=True))
    st4 = warm.run(reuse, deterministic=True)
    pc = st4["prefix_cache"]
    print(f"prefix reuse: {pc['hits']}/{pc['lookups']} admissions hit "
          f"the shared system prompt ({pc['hit_tokens']} prefill tokens "
          f"skipped, {pc['entries']} cached blocks resident, "
          f"{pc['cow_copies']} COW copies)")
    print(f"cached streams bitwise equal cache-off run: "
          f"{streams(warm) == streams(cold)}  "
          f"prefix-cache pool leak-free: {warm.pool.used_blocks == 0}")


if __name__ == "__main__":
    main()
