"""Kernel-level performance attribution: the always-on roofline ledger.

The fourth observability layer (after traces, streaming metrics, and the
fleet view): *where does the step time go, and how far from roofline is
each line?* Promoted from the PR-6 ``bench_step_ledger`` one-off into a
library with three pieces:

1. :class:`RooflineLedger` — itemizes a step into named components.
   Costs come from the ``cost_estimate=`` FLOPs/bytes every ``pallas_call``
   site already declares (``ops._common.kernel_cost_table`` — PTA003
   guarantees coverage) or from the analytic component specs
   (:func:`flagship_component_specs`); the per-platform peak-FLOPs table
   (``metrics.PEAK_FLOPS_TABLE``) and the HBM-bandwidth table below turn
   each line into a compute-/memory-bound classification with an
   achieved-vs-roofline fraction, and whatever the lines don't cover is an
   explicit ``unattributed`` remainder — the 0.38 gap becomes named lines,
   not a guess.
2. :func:`merge_device_trace` — joins ``jax.profiler`` device trace
   events against host-side chrome spans through the shared
   ``exporters.write_chrome_trace`` writer, one Perfetto view on a common
   clock (host spans + ``comm_span``/``named_scope`` sites + device
   kernel occupancy).
3. The measurement-only contract: nothing here touches the computation —
   model-mode costs are read at TRACE time from the cost-estimate table
   (zero device work), measured-mode components are timed in isolation.
   Losses with the ledger on are bit-identical to off (pinned by test).

Switched by ``PADDLE_TPU_LEDGER`` (+ ``PADDLE_TPU_LEDGER_DIR`` for JSONL
report output); ``jit.TrainStep(ledger=...)`` wins over the env.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Dict, List, Optional

from .. import envs
from .exporters import write_chrome_trace
from .metrics import peak_flops_info

ENV_LEDGER = "PADDLE_TPU_LEDGER"
ENV_LEDGER_DIR = "PADDLE_TPU_LEDGER_DIR"

# Per-chip HBM bandwidth (bytes/s) by PJRT device_kind substring, matched
# case-insensitively, FIRST match wins (same discipline as
# metrics.PEAK_FLOPS_TABLE). Datasheet numbers — achieved-vs-roofline
# fractions read against these are the conventional (conservative)
# roofline, not the measured-achievable ceiling bench.py's
# measured_hbm_bw() reports. No CPU row and no bare "v5" row, as in
# metrics.PEAK_FLOPS_TABLE: a device without a row has no rate.
HBM_BW_TABLE = (
    ("v6e", 1640e9), ("trillium", 1640e9),
    ("v5p", 2765e9),
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5litepod", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def hbm_bw_per_device(device=None):
    """(bytes/s, source) for one device from the table; (None,
    'unknown:<kind>') when the kind has no entry."""
    if device is None:
        import jax
        devs = jax.devices()
        if not devs:
            return None, "unknown:no-devices"
        device = devs[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, bw in HBM_BW_TABLE:
        if key in kind:
            return bw, f"table:{key}"
    return None, f"unknown:{kind or '?'}"


def ledger_enabled(explicit: Optional[bool] = None) -> bool:
    """Explicit argument wins; else the PADDLE_TPU_LEDGER env knob."""
    if explicit is not None:
        return bool(explicit)
    return bool(envs.get(ENV_LEDGER))


def ledger_dir() -> Optional[str]:
    """Report output directory: PADDLE_TPU_LEDGER_DIR, else the telemetry
    dir so one knob routes all observability files."""
    out = envs.get(ENV_LEDGER_DIR)
    if out:
        return out
    from .trace import telemetry_dir
    return telemetry_dir()


class RooflineLedger:
    """Itemized step-time ledger with per-line roofline classification.

    Two feeding modes, composable in one ledger:

    * **model mode** (the always-on ``TrainStep`` path): ``ingest()`` the
      per-program kernel-cost delta ``ops._common.kernel_costs_since``
      captures while the step lowers — each named pallas_call site becomes
      a line with its declared FLOPs/bytes, and the line's *roofline time*
      (max of compute and memory time at peak) is the attribution. Zero
      device work.
    * **measured mode** (bench / dryrun): ``add(..., time_ms=)`` each
      component timed in isolation (``flagship_component_specs`` provides
      the flagship step's component builders + analytic costs); the line
      then also carries ``achieved_frac`` — roofline time over measured
      time, i.e. how far from the hardware ceiling the component runs.

    ``report(step_time_ms)`` emits the lines plus an explicit
    ``unattributed`` remainder (step time minus attributed time, clamped
    at 0) so the gap is a first-class number, never an implication.
    """

    def __init__(self, name: str = "train_step",
                 peak_flops: Optional[float] = None,
                 hbm_bw: Optional[float] = None,
                 device=None, window: int = 64):
        self.name = name
        if peak_flops is not None:
            self.peak_flops, self.peak_source = float(peak_flops), "arg"
        else:
            self.peak_flops, self.peak_source = peak_flops_info(device)
        if hbm_bw is not None:
            self.hbm_bw, self.bw_source = float(hbm_bw), "arg"
        else:
            self.hbm_bw, self.bw_source = hbm_bw_per_device(device)
        self.components: Dict[str, Dict] = {}
        self._order: List[str] = []
        self.steps = 0
        self._step_ms: collections.deque = collections.deque(maxlen=window)

    # -- feeding -------------------------------------------------------------

    def add(self, name: str, flops: float = 0, bytes_accessed: float = 0,
            transcendentals: float = 0, time_ms: Optional[float] = None,
            calls: int = 1) -> Dict:
        """Add (or replace) one named component line."""
        if name not in self.components:
            self._order.append(name)
        entry = {"flops": float(flops),
                 "bytes_accessed": float(bytes_accessed),
                 "transcendentals": float(transcendentals),
                 "time_ms": time_ms if time_ms is None else float(time_ms),
                 "calls": int(calls)}
        self.components[name] = entry
        return entry

    def ingest(self, costs: Dict[str, Dict]) -> int:
        """Model-mode feed: one line per kernel from a
        ``kernel_costs_since`` delta (or the observed entries of
        ``kernel_cost_table``). Returns the number of lines added."""
        n = 0
        for name, rec in sorted(costs.items()):
            if not rec.get("calls"):
                continue
            self.add(name, flops=rec.get("flops") or 0,
                     bytes_accessed=rec.get("bytes_accessed") or 0,
                     transcendentals=rec.get("transcendentals") or 0,
                     calls=rec["calls"])
            n += 1
        return n

    def on_step(self, step_time_s: float) -> None:
        """Record one measured step wall time (host float, no sync)."""
        self.steps += 1
        if step_time_s and step_time_s > 0:
            self._step_ms.append(step_time_s * 1e3)

    # -- classification ------------------------------------------------------

    def classify(self, flops: float, bytes_accessed: float) -> Dict:
        """Roofline classification of one cost: time at peak compute, time
        at peak bandwidth, whichever dominates is the bound and the max is
        the roofline (best-achievable) time."""
        compute_ms = (flops / self.peak_flops * 1e3
                      if self.peak_flops else None)
        memory_ms = (bytes_accessed / self.hbm_bw * 1e3
                     if self.hbm_bw else None)
        if compute_ms is None and memory_ms is None:
            return {"compute_ms": None, "memory_ms": None,
                    "bound": "unknown", "roofline_ms": None}
        cm, mm = compute_ms or 0.0, memory_ms or 0.0
        return {"compute_ms": compute_ms, "memory_ms": memory_ms,
                "bound": "compute" if cm >= mm else "memory",
                "roofline_ms": max(cm, mm)}

    # -- reporting -----------------------------------------------------------

    def step_time_ms(self) -> Optional[float]:
        """Best recorded step time (best-of mirrors the bench convention:
        jitter is one-sided)."""
        return min(self._step_ms) if self._step_ms else None

    def report(self, step_time_ms: Optional[float] = None) -> Dict:
        """The itemized ledger: one dict per component line, each with its
        roofline classification, plus the explicit unattributed remainder.

        A line's *attributed* time is its measured ``time_ms`` when fed in
        measured mode, else its roofline time (an optimistic floor — real
        kernels run above roofline, so model-mode remainders are upper
        bounds on the true gap)."""
        step_ms = (float(step_time_ms) if step_time_ms is not None
                   else self.step_time_ms())
        lines = []
        attributed = 0.0
        for name in self._order:
            c = self.components[name]
            cls = self.classify(c["flops"], c["bytes_accessed"])
            t = c["time_ms"] if c["time_ms"] is not None \
                else cls["roofline_ms"]
            line = {"name": name, "calls": c["calls"],
                    "flops": c["flops"],
                    "bytes_accessed": c["bytes_accessed"],
                    "transcendentals": c["transcendentals"],
                    "time_ms": c["time_ms"], "attributed_ms": t,
                    "measured": c["time_ms"] is not None}
            line.update(cls)
            if c["time_ms"] and cls["roofline_ms"] is not None \
                    and c["time_ms"] > 0:
                line["achieved_frac"] = cls["roofline_ms"] / c["time_ms"]
            else:
                line["achieved_frac"] = None
            if step_ms and t is not None:
                line["frac_of_step"] = t / step_ms
            else:
                line["frac_of_step"] = None
            attributed += t or 0.0
            lines.append(line)
        out = {"name": self.name, "mode": "ledger",
               "peak_flops": self.peak_flops,
               "peak_source": self.peak_source,
               "hbm_bw": self.hbm_bw, "bw_source": self.bw_source,
               "steps": self.steps, "step_ms": step_ms,
               "attributed_ms": attributed, "lines": lines}
        if step_ms:
            un = max(step_ms - attributed, 0.0)
            out["unattributed_ms"] = un
            out["unattributed_frac"] = un / step_ms
            # the remainder is a LINE, not just a scalar: it renders in
            # the same table and is gated the same way as any component
            lines.append({"name": "unattributed", "calls": 0,
                          "flops": 0.0, "bytes_accessed": 0.0,
                          "transcendentals": 0.0, "time_ms": None,
                          "attributed_ms": un, "measured": False,
                          "compute_ms": None, "memory_ms": None,
                          "bound": "remainder", "roofline_ms": None,
                          "achieved_frac": None,
                          "frac_of_step": un / step_ms})
        else:
            out["unattributed_ms"] = None
            out["unattributed_frac"] = None
        return out

    def report_lines(self, step_time_ms: Optional[float] = None
                     ) -> List[str]:
        """Human-readable rendering of :meth:`report`."""
        rep = self.report(step_time_ms)
        hdr = f"RooflineLedger[{rep['name']}]"
        if rep["step_ms"]:
            hdr += f": step {rep['step_ms']:.3f} ms"
        if rep["unattributed_frac"] is not None:
            hdr += (f", unattributed {rep['unattributed_ms']:.3f} ms "
                    f"({rep['unattributed_frac'] * 100:.1f}%)")
        out = [hdr]
        for ln in rep["lines"]:
            t = ln["attributed_ms"]
            tstr = f"{t:.3f} ms" if t is not None else "?"
            bits = [f"  {ln['name']:<28}{tstr:>12}"]
            if ln["frac_of_step"] is not None:
                bits.append(f"{ln['frac_of_step'] * 100:5.1f}%")
            bits.append(f"[{ln['bound']}]")
            if ln["achieved_frac"] is not None:
                bits.append(f"roofline {ln['achieved_frac'] * 100:.0f}%")
            if not ln["measured"] and ln["bound"] not in ("remainder",):
                bits.append("(model)")
            out.append(" ".join(bits))
        return out

    def write(self, path: Optional[str] = None,
              step_time_ms: Optional[float] = None) -> Optional[str]:
        """Append one report record as a JSONL line (ledger dir default)."""
        if path is None:
            d = ledger_dir()
            if not d:
                return None
            path = os.path.join(d, f"ledger_{self.name}.jsonl")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        from .exporters import _jsonable
        with open(path, "a") as fh:
            fh.write(json.dumps(self.report(step_time_ms),
                                default=_jsonable) + "\n")
        return path


# ---------------------------------------------------------------------------
# flagship component specs (promoted from bench.py's bench_step_ledger)
# ---------------------------------------------------------------------------

def flagship_component_specs(config, batch: int, seq: int,
                             use_flash: bool = True, seed: int = 4):
    """The flagship train step itemized into measured-mode components.

    Returns a list of spec dicts — ``name``, ``build()`` (→ ``(fn, args)``
    to hand to the caller's timer), ``mult`` (L for per-layer components),
    and analytic ``flops`` / ``bytes_accessed`` / ``transcendentals`` for
    the roofline classification — covering attn/ffn/proj/head fwd+bwd,
    the AdamW update, and (zero on one chip) collectives. The caller owns
    the timer (bench.py uses device spans, the dryrun wall clock) and
    feeds ``RooflineLedger.add(name, ..., time_ms=mult * t)``.

    ``use_flash=False`` swaps the attention component to the dense
    (jnp) path for hosts where the Pallas kernels would interpret."""
    import jax as _jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.llama import count_params

    c = config
    B, S, H, I = batch, seq, c.hidden_size, c.intermediate_size
    L, nh, hd = c.num_hidden_layers, c.num_attention_heads, c.head_dim
    V = c.vocab_size
    it = jnp.dtype(c.dtype).itemsize
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) * 0.05,
                               c.dtype)
    sc = 1.0 / (hd ** 0.5)
    cf = 0.5 if use_flash else 1.0  # flash computes the causal half only

    def build_attn_fwd():
        q = f(B * nh, S, hd)
        if use_flash:
            from ..ops import flash_attention as _fa
            fn = lambda q, k, v: _fa._flash_fwd(q, k, v, True, sc,
                                                1024, 1024)[0]
        else:
            def fn(q, k, v):
                s_ = jnp.einsum("bqd,bkd->bqk", q, k) * sc
                mask = jnp.tril(jnp.ones((S, S), bool))
                s_ = jnp.where(mask, s_.astype(jnp.float32), -1e30)
                p = _jax.nn.softmax(s_, axis=-1).astype(q.dtype)
                return jnp.einsum("bqk,bkd->bqd", p, v)
        return fn, (q, q, q)

    def build_attn_bwd():
        fn_f, args = build_attn_fwd()
        loss = lambda *a: (fn_f(*a).astype(jnp.float32) ** 2).sum()
        fn = _jax.grad(loss, argnums=(0, 1, 2))
        return (lambda q, k, v: fn(q, k, v)), args

    def build_ffn_fwd():
        x = f(B * S, H)
        wg, wu, wd = f(H, I), f(H, I), f(I, H)
        fn = lambda x, wg, wu, wd: (_jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return fn, (x, wg, wu, wd)

    def build_ffn_bwd():
        fn_f, args = build_ffn_fwd()
        loss = lambda *a: (fn_f(*a).astype(jnp.float32) ** 2).sum()
        return _jax.grad(loss, argnums=(0, 1, 2, 3)), args

    def build_proj_fwd():
        x = f(B * S, H)
        wq, wo = f(H, 4 * H), f(H, H)  # fused qkv + q-sized o proj
        fn = lambda x, wq, wo: (x @ wq)[:, :H] @ wo
        return fn, (x, wq, wo)

    def build_proj_bwd():
        fn_f, args = build_proj_fwd()
        loss = lambda *a: (fn_f(*a).astype(jnp.float32) ** 2).sum()
        return _jax.grad(loss, argnums=(0, 1, 2)), args

    labels = jnp.asarray(rng.randint(0, V, (B * S,)), jnp.int32)

    def head_loss(x, wv):
        logits = (x @ wv).astype(jnp.float32)
        return -jnp.take_along_axis(
            _jax.nn.log_softmax(logits, -1), labels[:, None], 1).mean()

    def build_head_fwd():
        return head_loss, (f(B * S, H), f(H, V))

    def build_head_bwd():
        _, args = build_head_fwd()
        return _jax.grad(head_loss, argnums=(0, 1)), args

    P = count_params(c)

    def build_opt():
        p_ = f(P)
        m_ = jnp.zeros((P,), jnp.float32)
        v_ = jnp.zeros((P,), jnp.float32)
        g_ = f(P)

        def adamw(p, m, v, g):
            g32 = g.astype(jnp.float32)
            m2 = 0.9 * m + 0.1 * g32
            v2 = 0.999 * v + 1e-3 * g32 * g32
            return ((p.astype(jnp.float32)
                     - 1e-4 * (m2 / (jnp.sqrt(v2) + 1e-8) + 0.1
                               * p.astype(jnp.float32))).astype(p.dtype),
                    m2, v2)
        return adamw, (p_, m_, v_, g_)

    attn_flops = 2 * 2 * B * nh * S * S * hd * cf
    attn_bytes = 4 * B * nh * S * hd * it
    attn_trans = B * nh * S * S * cf
    ffn_flops = 3 * 2 * B * S * H * I
    ffn_bytes = (2 * B * S * H + 2 * B * S * I + 3 * H * I) * it
    proj_flops = 2 * B * S * H * (4 * H) + 2 * B * S * H * H
    proj_bytes = (B * S * 6 * H + 5 * H * H) * it
    head_flops = 2 * B * S * H * V
    head_bytes = (B * S * H + H * V) * it + 4 * B * S * V
    # AdamW streams bf16 param + f32 m/v in AND out; elementwise FLOPs
    opt_bytes = 2 * P * (it + 4 + 4)
    return [
        {"name": "attention_fwd", "build": build_attn_fwd, "mult": L,
         "flops": attn_flops, "bytes_accessed": attn_bytes,
         "transcendentals": attn_trans},
        {"name": "attention_bwd", "build": build_attn_bwd, "mult": L,
         # bwd recomputes p and runs 5 matmuls vs the fwd's 2
         "flops": 2.5 * attn_flops, "bytes_accessed": 2 * attn_bytes,
         "transcendentals": attn_trans},
        {"name": "ffn_fwd", "build": build_ffn_fwd, "mult": L,
         "flops": ffn_flops, "bytes_accessed": ffn_bytes,
         "transcendentals": B * S * I},
        {"name": "ffn_bwd", "build": build_ffn_bwd, "mult": L,
         "flops": 2 * ffn_flops, "bytes_accessed": 2 * ffn_bytes,
         "transcendentals": B * S * I},
        {"name": "qkvo_proj_fwd", "build": build_proj_fwd, "mult": L,
         "flops": proj_flops, "bytes_accessed": proj_bytes,
         "transcendentals": 0},
        {"name": "qkvo_proj_bwd", "build": build_proj_bwd, "mult": L,
         "flops": 2 * proj_flops, "bytes_accessed": 2 * proj_bytes,
         "transcendentals": 0},
        {"name": "lm_head_loss_fwd", "build": build_head_fwd, "mult": 1,
         "flops": head_flops, "bytes_accessed": head_bytes,
         "transcendentals": B * S * V},
        {"name": "lm_head_loss_bwd", "build": build_head_bwd, "mult": 1,
         "flops": 2 * head_flops, "bytes_accessed": 2 * head_bytes,
         "transcendentals": B * S * V},
        {"name": "optimizer", "build": build_opt, "mult": 1,
         "flops": 10 * P, "bytes_accessed": opt_bytes,
         "transcendentals": P},
    ]


# ---------------------------------------------------------------------------
# device-trace merge
# ---------------------------------------------------------------------------

_HOST_PID = 9000  # host streams re-pid'd above any real device pid


def load_device_trace_events(profile_dir: str) -> List[Dict]:
    """All chrome trace events from a ``jax.profiler.trace`` output tree
    (``**/*.trace.json.gz`` + plain ``.trace.json``)."""
    events: List[Dict] = []
    paths = (glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                       recursive=True)
             + glob.glob(os.path.join(profile_dir, "**", "*.trace.json"),
                         recursive=True))
    for fpath in sorted(paths):
        opener = gzip.open if fpath.endswith(".gz") else open
        try:
            with opener(fpath, "rt") as fh:
                tr = json.load(fh)
        except (OSError, ValueError):
            continue
        events.extend(tr.get("traceEvents") or [])
    return events


def merge_device_trace(profile_dir: str, host_events=None,
                       out_path: Optional[str] = None,
                       align_on: Optional[str] = None) -> Dict:
    """One Perfetto view: device kernel occupancy + host spans, common clock.

    ``profile_dir`` is a ``jax.profiler.trace`` output directory;
    ``host_events`` an iterable of chrome trace-event dicts (µs timebase)
    from any of the repo's host-side producers (``Profiler.export``,
    ``RequestTracer.to_chrome_events``, hand-built spans around step
    components). Device and host streams carry unrelated clocks, so both
    are shifted to a common zero: when ``align_on`` names a span present
    in BOTH streams (e.g. a ``jax.named_scope`` annotation that shows up
    in the device trace's XLA-op metadata and as a host span), the first
    occurrence on each side is pinned to the same instant; otherwise each
    stream's earliest timestamped event becomes t=0 (min-ts alignment —
    coarser, but ordering within each stream is exact).

    Host events are re-pid'd to a dedicated ``host`` process row so they
    never collide with device pids. Writes through the shared
    ``write_chrome_trace`` writer and returns a summary dict."""
    device_events = load_device_trace_events(profile_dir)
    host_events = list(host_events or [])

    def first_ts(evts, name=None):
        ts = [e["ts"] for e in evts
              if e.get("ts") is not None and e.get("ph") != "M"
              and (name is None or name in str(e.get("name", "")))]
        return min(ts) if ts else None

    aligned_on = None
    d0 = h0 = None
    if align_on:
        d0 = first_ts(device_events, align_on)
        h0 = first_ts(host_events, align_on)
        if d0 is not None and h0 is not None:
            aligned_on = align_on
    if aligned_on is None:
        d0 = first_ts(device_events)
        h0 = first_ts(host_events)

    merged: List[Dict] = [
        {"name": "process_name", "ph": "M", "pid": _HOST_PID,
         "args": {"name": "host (paddle_tpu spans)"}},
    ]
    for e in host_events:
        e = dict(e)
        e["pid"] = _HOST_PID + int(e.get("pid", 0) or 0)
        if e.get("ts") is not None and h0 is not None:
            e["ts"] = e["ts"] - h0
        merged.append(e)
    for e in device_events:
        e = dict(e)
        if e.get("ts") is not None and d0 is not None:
            e["ts"] = e["ts"] - d0
        merged.append(e)
    out = {"device_events": len(device_events),
           "host_events": len(host_events),
           "aligned_on": aligned_on,
           "out_path": None}
    if out_path:
        out["out_path"] = write_chrome_trace(out_path, merged)
    else:
        out["events"] = merged
    return out
