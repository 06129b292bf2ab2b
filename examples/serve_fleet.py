"""Serve one trace with a multi-replica fleet: prefix-affinity routing,
a replica crash healed from its journal, and a zero-downtime rolling
weight swap.

The FleetRouter owns three InferenceEngine replicas. Every submit
probes each live replica's prefix cache host-side and routes to the
one already holding the longest cached prefix (ties broken by a
composite load signal, then replica index — fully deterministic), so
requests sharing a system prompt concentrate where their COW blocks
live instead of spreading the cache 1/N thin. A spill threshold keeps
adversarial skew from starving the other replicas.

Act 2 kills a replica mid-burst: its journal fd dies unflushed, the
router re-drives every accepted-but-unfinished request in the journal
onto survivors, and — because greedy decode is a pure function of
(prompt + weights) — the migrated streams come out bit-identical to a
run with no failure at all. Zero accepted requests are lost.

Act 3 rolls new weights across the fleet one replica at a time: each
is steered out of routing, drains to its idle boundary, swaps, and
rejoins while the others keep serving. Zero downtime, zero drops.

Tiny model on CPU (pallas interpret); the same router drives real
fleets on TPU (see bench.py serve_fleet).
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
    from paddle_tpu.inference import FleetRouter, ServeConfig
    from paddle_tpu.inference import InferenceEngine, Request
    from paddle_tpu.models.llama import init_llama_params, llama_tiny

    config = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                        seq=512)
    params = init_llama_params(config, seed=0)
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256,
                        prefix_cache=True)

    rng = np.random.RandomState(0)
    system = rng.randint(1, config.vocab_size, size=140).tolist()

    def mk_trace():
        # even requests share the 140-token system prompt (affinity
        # bait spanning a full KV block); odd ones are short one-offs
        out = []
        for i in range(8):
            if i % 2 == 0:
                prompt = system + rng.randint(
                    1, config.vocab_size, size=8).tolist()
            else:
                prompt = rng.randint(1, config.vocab_size,
                                     size=24).tolist()
            out.append(Request(prompt, max_new_tokens=5,
                               arrival=float(i)))
        return out
    trace = mk_trace()

    def fresh():
        return [Request(list(r.prompt), max_new_tokens=r.max_new_tokens,
                        arrival=r.arrival) for r in trace]

    # the bit-identity oracle: the same trace on ONE lone engine
    lone = InferenceEngine(params, config, serve)
    ref_reqs = fresh()
    for i, r in enumerate(ref_reqs):
        r.request_id = i
    lone.run(ref_reqs, deterministic=True)
    reference = {s.req.request_id: list(s.generated)
                 for s in lone.finished}

    # ---- act 1: prefix-affinity routing over 3 replicas ----
    out = tempfile.mkdtemp(prefix="paddle_tpu_fleet_")
    os.mkdir(os.path.join(out, "a1"))
    fleet = FleetRouter(params, config, serve, n_replicas=3,
                        journal_dir=os.path.join(out, "a1"))
    stats = fleet.run(fresh(), deterministic=True)
    print(f"fleet of {stats['replicas']}: {stats['requests']} requests, "
          f"{stats['generated_tokens']} tokens in "
          f"{stats['iterations']} iterations")
    print(f"routing: {stats['routed_per_replica']} per replica, "
          f"affinity hits {stats['affinity_hits']} "
          f"(hit rate {stats['affinity_hit_rate']:.2f}), "
          f"spills {stats['spills']}")
    print(f"fleet streams bit-identical to lone engine: "
          f"{fleet.streams() == reference}")

    # ---- act 2: kill a replica mid-burst, journal migration ----
    os.mkdir(os.path.join(out, "a2"))
    chaos = FleetRouter(params, config, serve, n_replicas=3,
                        journal_dir=os.path.join(out, "a2"))
    st2 = chaos.run(fresh(), deterministic=True, kill_at=(6, 0))
    print(f"replica 0 killed at iteration 6: "
          f"{st2['migrations']} requests re-driven from its journal, "
          f"{st2['lost']} lost")
    print(f"migrated streams bit-identical to no-failure run: "
          f"{chaos.streams() == reference}  survivors leak-free: "
          f"{all(chaos.engines[i].pool.used_blocks == 0 for i in chaos._live())}")

    # ---- act 3: rolling fleet-wide weight swap, zero drops ----
    os.mkdir(os.path.join(out, "a3"))
    roll = FleetRouter(params, config, serve, n_replicas=3,
                       journal_dir=os.path.join(out, "a3"))
    st3 = roll.run(fresh(), deterministic=True, rolling_swap_at=3,
                   swap_source=params)
    drops = sum(e.last_swap["in_flight_running"]
                + e.last_swap["in_flight_prefill"]
                for e in roll.engines)
    print(f"rolling swap: {st3['rolling_swaps']} replicas swapped at "
          f"their idle boundaries, {drops} requests caught in flight, "
          f"{st3['lost']} lost")
    print(f"post-swap streams bit-identical (same weights): "
          f"{roll.streams() == reference}")

    # one fleet scrape: every replica's metrics label-split + the
    # router's own block
    prom = roll.render_prometheus()
    lines = [ln for ln in prom.splitlines()
             if ln.startswith("paddle_tpu_fleet_ro")]
    print(f"merged exposition: {len(prom.splitlines())} lines, e.g.")
    for ln in lines:
        print(f"  {ln}")


if __name__ == "__main__":
    main()
