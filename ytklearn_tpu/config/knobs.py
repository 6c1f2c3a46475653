"""Central registry of every ``YTK_*`` environment knob.

Before this module each subsystem read ``os.environ`` directly, so the
set of runtime knobs was only discoverable by grepping and half of them
never reached docs/running_guide.md. Now every knob is *declared* here —
name, type, default, one-line doc — and every read goes through the typed
accessors below. The ytklint ``undeclared-knob`` rule forbids YTK_*
``os.environ`` reads anywhere else in the tree, and ``check_doc_sync``
asserts this registry and the running-guide knob table match both ways
(scripts/check_lint.sh runs both on every change).

Accessors re-read ``os.environ`` on every call: tests and operators set
knobs at runtime and the previous call sites were all live reads too.
The handful of knobs consumed by shell launchers (bin/*.sh) are declared
with ``scope="shell"`` so the doc table stays the one complete inventory.

Regenerate the running-guide table after editing declarations:

    python -m ytklearn_tpu.config.knobs regen docs/running_guide.md
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Dict, Optional

__all__ = [
    "Knob",
    "KNOBS",
    "get_raw",
    "get_str",
    "get_int",
    "get_float",
    "get_bool",
    "names",
    "table_markdown",
    "check_doc_sync",
    "sync_doc",
]


@dataclass(frozen=True)
class Knob:
    name: str
    type: str  # "str" | "int" | "float" | "bool"
    default: object  # parsed value returned when the env var is unset
    doc: str  # one line; becomes the running-guide table row
    scope: str = "lib"  # "lib" | "bench" | "shell" (bin/*.sh) | "test"


KNOBS: Dict[str, Knob] = {}


def _knob(name: str, type_: str, default, doc: str, scope: str = "lib") -> None:
    if name in KNOBS:
        raise ValueError(f"duplicate knob declaration: {name}")
    KNOBS[name] = Knob(name, type_, default, doc, scope)


# -- platform / launcher ----------------------------------------------------
_knob("YTK_MASTER_LOG", "str", "log/master.log",
      "merged rank-labeled master-log path for `bin/cluster_optimizer.sh`",
      scope="shell")
_knob("YTK_SLAVE_HOSTS", "str", None,
      "space-separated hosts for ranks 1..N-1 (`bin/cluster_optimizer.sh` "
      "ssh fan-out; unset = all ranks fork locally)", scope="shell")
_knob("YTK_COORDINATOR_HOST", "str", "127.0.0.1",
      "jax.distributed coordinator host for multi-host launches",
      scope="shell")
_knob("YTK_COORDINATOR_PORT", "int", 29401,
      "jax.distributed coordinator port", scope="shell")

# -- ingest -----------------------------------------------------------------
_knob("YTK_NO_NATIVE", "bool", False,
      "disable the native C++ libsvm parser (python fallback)")
_knob("YTK_SKETCH_ROWS", "int", 1 << 25,
      "rows above which quantile binning streams through the GK sketch "
      "instead of the full-sort path")

# -- convex training (blocked evaluation) -----------------------------------
_knob("YTK_ROW_CHUNK", "int", None,
      "fixed row-chunk override for blocked convex evaluation "
      "(see [models.md](models.md) \"Memory\")")
_knob("YTK_CHUNK_BUDGET_MB", "int", 1024,
      "score-intermediate memory budget that sizes the automatic row chunk")

# -- gbdt engine ------------------------------------------------------------
_knob("YTK_PROFILE_DIR", "str", None,
      "write a jax.profiler trace of the training loop for xprof")
_knob("YTK_GOSS_A", "float", 1.0,
      "GOSS top-gradient-magnitude keep fraction per tree in the device "
      "GBDT engine; a value < 1 enables gradient-based one-side sampling")
_knob("YTK_GOSS_B", "float", 0.1,
      "GOSS sample rate on the non-top remainder (sampled rows carry the "
      "1/b gradient-amplification correction); active only when "
      "`YTK_GOSS_A` < 1")
_knob("YTK_EFB", "bool", True,
      "exclusive feature bundling at GBDT binning time: merge mutually-"
      "exclusive sparse columns into offset-binned bundles (no-op when "
      "no such columns exist)")
_knob("YTK_EFB_CONFLICT", "int", 0,
      "max conflicting rows tolerated per EFB bundle (0 = strictly "
      "exclusive, lossless; >0 trades exactness for wider bundles)")

# -- observability ----------------------------------------------------------
_knob("YTK_OBS", "str", None,
      "`1` enables obs collection without export; `0` force-disables "
      "(wins over the trace-path knobs)")
_knob("YTK_TRACE", "str", None,
      "enable obs + write a Chrome-trace/Perfetto JSON to this path at exit")
_knob("YTK_TRACE_JSONL", "str", None,
      "enable obs + write the JSONL event stream to this path at exit")
_knob("YTK_TRACE_SAMPLE", "float", 0.01,
      "serve-side request-tracing head-sample rate: the fraction of "
      "/predict requests whose per-hop spans are recorded and kept as "
      "exemplars (deterministic counter-hashed draws; `0` disables the "
      "tracing plane, `1` = always-on — see "
      "[observability.md](observability.md))")
_knob("YTK_TRACE_SEED", "int", 0,
      "seed for the deterministic trace head sampler (same seed + same "
      "request order = same kept set)")
_knob("YTK_TRACE_EXEMPLARS", "int", 256,
      "per-process exemplar-ring capacity (kept request traces), exported "
      "at `/admin/traces`; shed/504/SLO-violating requests are always "
      "retained, head-sampled ones ride the ring too")
_knob("YTK_OBS_HISTORY_N", "int", 256,
      "per-metric time-series ring length for the metrics history plane "
      "(`/metrics?history=1`); `0` disables history sampling")
_knob("YTK_OBS_HISTORY_S", "float", 1.0,
      "metrics-history sampling interval in seconds (the obs heartbeat "
      "sampler thread snapshots every counter/gauge this often)")
_knob("YTK_PROF", "str", None,
      "profiling plane (ytkprof): `1` arms phase accounting, the compile "
      "ledger, and the memory-watermark sampler; a *path* additionally "
      "captures `jax.profiler.trace` output for capture-opted phases into "
      "that directory (Perfetto-loadable); unset/`0` = off with zero new "
      "per-call work on the span hot path — see "
      "[observability.md](observability.md) \"Profiling plane\"")
_knob("YTK_PROF_TOPK", "int", 10,
      "rows kept in the ytkprof top-k kernel table (per parsed capture "
      "and in the `ytkprof` report schema)")
_knob("YTK_PROF_MEM_S", "float", 0.5,
      "memory-watermark sampler interval in seconds (device bytes-in-use "
      "+ host RSS into bounded rings, peaks attributed to the enclosing "
      "profiler phase)")
_knob("YTK_PROF_LEDGER_N", "int", 512,
      "compile-ledger ring capacity: the newest N jit compiles kept with "
      "program label, abstract arg signature, and compile ms")
_knob("YTK_QUALITY_SAMPLE", "float", 0.05,
      "model-quality plane row-sample rate: the fraction of served rows "
      "whose feature values and scores feed the per-model drift sketches "
      "(deterministic counter-hashed draws; `0` disables the plane, `1` "
      "= every row — see [observability.md](observability.md) "
      "\"Model-quality plane\")")
_knob("YTK_QUALITY_SEED", "int", 0,
      "seed for the deterministic quality row sampler (same seed + same "
      "row order = same sampled set)")
_knob("YTK_QUALITY_B", "int", 64,
      "entry budget per weighted-GK quality sketch (training sidecar and "
      "serve-side streaming sketches; bounds both memory and the "
      "/metrics?quality=1 export size)")
_knob("YTK_QUALITY_EVAL_S", "float", 5.0,
      "quality-evaluator tick interval in seconds: each tick drains the "
      "sampled-row buffers into the sketches, recomputes PSI/KS and "
      "calibration drift, and feeds the drift sentinels")
_knob("YTK_MODEL_METRICS_MAX", "int", 32,
      "named per-model metric-family budget for the mesh-obs accounting "
      "plane (`serve.model.<name>.*` counters, latency rings, burn "
      "sentinels); names past the budget — and 404 name floods — land "
      "in the shared `__overflow__` bucket, so label cardinality is "
      "bounded by construction — see "
      "[observability.md](observability.md) \"Per-model accounting\"")

# -- run health -------------------------------------------------------------
_knob("YTK_HEALTH", "bool", True,
      "run-health sentinels (NaN/divergence/ingest-rate); `0` reduces every "
      "check to one attribute load")
_knob("YTK_HEALTH_STRICT", "bool", False,
      "escalate sentinel hits to HealthError naming the flight dump "
      "(unattended production runs)")
_knob("YTK_HEALTH_INGEST_TOL", "float", 0.01,
      "ingest error-rate threshold (fraction) for the parse sentinel")
_knob("YTK_SLO_BURN_WINDOW", "int", 256,
      "requests per SLO burn-rate window: the `health.slo_burn` sentinel "
      "judges the violation rate once per full window")
_knob("YTK_SLO_BURN_BUDGET", "float", 0.1,
      "SLO error budget as a windowed violation-rate fraction: when more "
      "than this fraction of a window's requests exceed the SLO (or are "
      "shed/504'd), `health.slo_burn` fires (strict mode escalates)")
_knob("YTK_HEALTH_DRIFT_PSI", "float", 0.25,
      "per-feature population-stability-index threshold for the serving "
      "drift sentinel: consecutive quality-evaluator ticks with any "
      "feature's PSI above it fire `health.drift` (0.1/0.25 are the "
      "conventional watch/act levels)")
_knob("YTK_HEALTH_DRIFT_KS", "float", 0.35,
      "per-feature Kolmogorov-Smirnov distance threshold for the serving "
      "drift sentinel (fires `health.drift` alongside the PSI test)")
_knob("YTK_HEALTH_DRIFT_WINDOWS", "int", 2,
      "consecutive over-threshold quality-evaluator ticks required before "
      "`health.drift` / `health.calibration` fire (one noisy tick cannot "
      "page anyone); the streak re-arms after each fire")
_knob("YTK_HEALTH_DRIFT_MIN_ROWS", "int", 200,
      "minimum sampled rows before the drift/calibration sentinels judge "
      "a model (a two-request warmup is not a distribution)")
_knob("YTK_HEALTH_CALIBRATION_TOL", "float", 0.1,
      "calibration-drift tolerance: absolute |mean predicted score - "
      "training-sidecar mean| (on the prediction scale) above which "
      "`health.calibration` fires")
_knob("YTK_FLIGHT", "bool", True,
      "flight-recorder auto-install in trainers; `0` opts out")
_knob("YTK_FLIGHT_N", "int", 4096,
      "flight-recorder event-ring capacity")
_knob("YTK_FLIGHT_DIR", "str", "flight_dumps",
      "flight-dump directory (default: `flight_dumps/`, which is "
      "gitignored — a crash dump must never end up committed)")

# -- resilience (docs/fault_tolerance.md) -----------------------------------
_knob("YTK_CHAOS", "str", None,
      "deterministic fault injection spec `site:kind:rate:seed[,...]` "
      "(kinds: oserror|error|sigterm|kill); counter-based draws make "
      "every injected fault reproducible — see "
      "[fault_tolerance.md](fault_tolerance.md)")
_knob("YTK_RETRY_MAX", "int", 4,
      "attempt budget per `resilience.retry` site (1 = no retries)")
_knob("YTK_RETRY_BASE_S", "float", 0.05,
      "first-retry backoff in seconds (doubles per attempt, "
      "deterministically jittered into [0.5, 1.0)x)")
_knob("YTK_RETRY_MAX_S", "float", 2.0,
      "backoff ceiling in seconds for the retry exponential")
_knob("YTK_PREEMPT", "bool", True,
      "preemption guard in trainers: SIGTERM/SIGINT deferred to the next "
      "round/iteration boundary, emergency checkpoint, exit 128+signum "
      "(`--resume auto` re-enters training); `0` keeps raw signal "
      "semantics")
_knob("YTK_RETRAIN_LOCK_TTL_S", "float", 900.0,
      "retrain lockfile heartbeat staleness (seconds) after which a new "
      "retrain auto-reclaims the lock; same-host dead owners are "
      "reclaimed immediately")

# -- continual training -----------------------------------------------------
_knob("YTK_GATE_COMPILED", "bool", True,
      "route the continual gate's held-out eval through CompiledScorer "
      "(batched jit scoring); `0` falls back to the host row walk")
_knob("YTK_CONTINUAL_BAND", "float", 0.0,
      "relative held-out-loss tolerance for retrain promotion: a candidate "
      "passes the metric gate when loss <= incumbent * (1 + band); 0 = "
      "must be no worse (config `continual.band` overrides per run)")
_knob("YTK_CONTINUAL_KEEP", "int", 2,
      "archived incumbent versions kept next to the model path for "
      "`ytklearn-tpu retrain --rollback`")
_knob("YTK_CONTINUAL_STRICT", "bool", False,
      "escalate a rejected retrain candidate to a non-zero exit "
      "(unattended freshness pipelines; default records the rejection "
      "and keeps the incumbent)")
_knob("YTK_CONTINUAL_DRIFT_URL", "str", None,
      "serving base URL (e.g. `http://127.0.0.1:8080`) the retrain "
      "driver fetches `/metrics?quality=1` from: the serve-side drift "
      "snapshot is recorded as an ADVISORY gate input (never pass/fail) "
      "in the gate report and result JSON — the hook drift-gated "
      "retraining hardens later")

# -- serving ----------------------------------------------------------------
_knob("YTK_SERVE_LADDER", "str", None,
      "serving batch-shape ladder, e.g. `1,8,64,512` "
      "(see [serving.md](serving.md))")
_knob("YTK_SERVE_WATCH_S", "float", 5.0,
      "serving hot-reload fingerprint poll interval in seconds "
      "(`0` disables the watcher)")
_knob("YTK_SERVE_REPLICAS", "int", 0,
      "serving fleet size: replica worker processes behind the front "
      "(`0` = single-process serving, `-1` = one per two CPU cores; "
      "CPU hosts only; CLI `--replicas` overrides — see "
      "[serving.md](serving.md))")
_knob("YTK_SERVE_SLO_MS", "float", 100.0,
      "serving p99 latency SLO in ms — the target the AIMD batch-size "
      "controller searches under (`0` disables the controller and "
      "restores the fixed `--max-batch`/`--max-wait-ms` knobs)")
_knob("YTK_SERVE_SLO_MODELS", "str", None,
      "per-model SLO overrides for the mesh-obs burn sentinels, "
      "`name:ms,name2:ms` (e.g. `ctr:25,ranker:100`); listed models get "
      "their own `health.slo_burn` budget at that SLO, unlisted models "
      "inherit the app-wide `--slo-ms` default — see "
      "[observability.md](observability.md) \"Per-model accounting\"")
_knob("YTK_SERVE_CACHE_ROWS", "int", 0,
      "bounded LRU prediction-cache capacity in rows, keyed on (model "
      "fingerprint, feature-row hash); hits bypass the batcher queue and "
      "are bit-identical to the scored path (`0` disables)")
_knob("YTK_SERVE_AIMD_INC", "int", 8,
      "AIMD additive-increase step in rows per clean adjustment window "
      "(the raw target then snaps DOWN to a compiled ladder rung)")
_knob("YTK_SERVE_AIMD_BACKOFF", "float", 0.5,
      "AIMD multiplicative backoff factor applied to the raw batch "
      "target on a p99-SLO violation (must be in (0, 1))")
_knob("YTK_SERVE_FUSED", "bool", False,
      "serve-side fused Pallas GBDT traversal kernel (bit-identical "
      "math, heap node layout resident in VMEM); falls back to the "
      "stacked XLA path with a `serve.downgrade.*` counter when Mosaic "
      "cannot compile it — see [serving.md](serving.md)")
_knob("YTK_SERVE_BINNED", "bool", False,
      "binned GBDT scoring rung: bin request rows once per batch "
      "(dumped `<model>.bins.json` training edges, else ensemble-derived "
      "thresholds — the latter bit-identical) and traverse on "
      "uint8/uint16 bin indices via the fastest backend (Pallas on TPU, "
      "native C++ on CPU, XLA fallback)")
_knob("YTK_SERVE_PRECISION", "str", "f64",
      "serving precision rung for the convex/FM/FFM einsum scorers: "
      "`bf16` = bf16 operands with f32 accumulation (quality band "
      "measured in scripts/serve_bench.py); GBDT/GBST scoring ignores it")
_knob("YTK_SERVE_KERNEL_THREADS", "int", 0,
      "row-parallel threads for the native serve kernel "
      "(0 = min(8, cores); batches under 64 rows stay single-threaded)")
_knob("YTK_SERVE_AIMD_WINDOW", "int", 16,
      "batches per AIMD adjustment window: the controller judges the "
      "window's worst observed request latency against the SLO once per "
      "window, so one straggler cannot collapse the batch size")
_knob("YTK_SERVE_REPLICAS_MIN", "int", 0,
      "fleet autoscaler floor: minimum replica slots the autoscaler may "
      "reap down to (`0` = follow `--replicas`; CLI `--replicas-min` "
      "overrides — see [serving.md](serving.md) autoscaling)")
_knob("YTK_SERVE_REPLICAS_MAX", "int", 0,
      "fleet autoscaler ceiling: maximum replica slots the autoscaler "
      "may grow to (`0` = follow `--replicas`, which disarms "
      "autoscaling; CLI `--replicas-max` overrides)")
_knob("YTK_SERVE_SCALE_INTERVAL_S", "float", 1.0,
      "autoscaler decision-tick interval in seconds (each tick samples "
      "the windowed load signals and advances the hysteresis streaks)")
_knob("YTK_SERVE_SCALE_UP_BACKLOG", "float", 256.0,
      "scale-up backlog threshold in queued+in-flight rows PER READY "
      "REPLICA: a tick above it (or any shed / p99-over-SLO / slo-burn "
      "fire) counts as overloaded")
_knob("YTK_SERVE_SCALE_DOWN_BACKLOG", "float", 16.0,
      "scale-down backlog threshold in rows per ready replica: a tick "
      "below it with zero sheds and p99 comfortably inside the SLO "
      "counts as idle (the gap up to the scale-up threshold is the "
      "hysteresis band)")
_knob("YTK_SERVE_SCALE_UP_WINDOWS", "int", 3,
      "consecutive overloaded ticks required before the autoscaler "
      "grows the fleet (one bursty tick cannot spawn a replica)")
_knob("YTK_SERVE_SCALE_DOWN_WINDOWS", "int", 10,
      "consecutive idle ticks required before the autoscaler reaps a "
      "replica (drain-based: fenced, completed/rerouted, then SIGTERM)")
_knob("YTK_SERVE_SCALE_UP_COOLDOWN_S", "float", 5.0,
      "seconds after a scale-up before the next scale-up may fire (new "
      "capacity must land and be judged before growing again)")
_knob("YTK_SERVE_SCALE_DOWN_COOLDOWN_S", "float", 30.0,
      "seconds after ANY scale decision before a scale-down may fire "
      "(capacity a spike just paid for is never reaped immediately)")

# -- transform pipeline -----------------------------------------------------
_knob("YTK_TRANSFORM_CACHE", "int", 1_000_000,
      "bound on the serve-time feature-hash resolution cache (raw name "
      "-> scoring column + murmur sign, per loaded model); at the bound "
      "new names compute uncached, so a fresh-name flood costs cpu, "
      "never memory")

# -- bench ------------------------------------------------------------------
_knob("YTK_HIGGS_DIR", "str", None,
      "directory holding the real Higgs split for bench.py "
      "(default: `experiment/higgs/`)", scope="bench")
_knob("YTK_REF", "str", "/root/reference",
      "path to the reference checkout used by reference-gated tests and "
      "benches", scope="test")
_knob("YTK_LOCKWATCH_HOLD_MS", "float", 1000.0,
      "lock hold-time budget (ms) for `pytest --ytk-lockwatch`: a "
      "watched lock held longer fails the `@pytest.mark.threaded` test "
      "(the runtime twin of ytklint blocking-call-under-lock)",
      scope="test")


# ---------------------------------------------------------------------------
# Typed accessors — the only sanctioned YTK_* environ reads in the tree.
# ---------------------------------------------------------------------------

_FALSY = ("0", "false", "no", "off")


def _declared(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"undeclared knob {name!r}: declare it in "
            "ytklearn_tpu/config/knobs.py (the ytklint undeclared-knob "
            "rule enforces this statically too)"
        ) from None


def get_raw(name: str) -> Optional[str]:
    """The raw env string, or None when unset (tri-state knobs: YTK_OBS)."""
    _declared(name)
    return os.environ.get(name)


def get_str(name: str) -> Optional[str]:
    knob = _declared(name)
    raw = os.environ.get(name)
    return raw if raw not in (None, "") else knob.default


def get_int(name: str) -> Optional[int]:
    knob = _declared(name)
    raw = os.environ.get(name)
    return int(raw) if raw not in (None, "") else knob.default


def get_float(name: str) -> Optional[float]:
    knob = _declared(name)
    raw = os.environ.get(name)
    return float(raw) if raw not in (None, "") else knob.default


def get_bool(name: str) -> bool:
    """Unset or empty -> declared default (an empty export is "cleared",
    same as the str/int/float accessors); `0`/`false`/`no`/`off` (any
    case) -> False; anything else -> True."""
    knob = _declared(name)
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return bool(knob.default)
    return raw.strip().lower() not in _FALSY


def names() -> list:
    return sorted(KNOBS)


# ---------------------------------------------------------------------------
# Doc sync: the running-guide knob table is generated from this registry.
# ---------------------------------------------------------------------------

DOC_BEGIN = "<!-- knob-table:begin -->"
DOC_END = "<!-- knob-table:end -->"
_NAME_RE = re.compile(r"`(YTK_[A-Z0-9_]+)")


def _fmt_default(knob: Knob) -> str:
    if knob.default is None:
        return "unset"
    if knob.type == "bool":
        return "on" if knob.default else "off"
    return f"`{knob.default}`"


def table_markdown() -> str:
    """The complete knob table as a markdown block (with sync markers)."""
    lines = [DOC_BEGIN, "| knob | default | effect |", "|---|---|---|"]
    for name in sorted(KNOBS):
        knob = KNOBS[name]
        suffix = {"shell": " *(shell launchers)*", "bench": " *(bench.py)*",
                  "test": " *(tests)*"}.get(knob.scope, "")
        lines.append(f"| `{name}` | {_fmt_default(knob)} | {knob.doc}{suffix} |")
    lines.append(DOC_END)
    return "\n".join(lines)


def _doc_block(text: str, path: str) -> str:
    try:
        start = text.index(DOC_BEGIN)
        end = text.index(DOC_END)
    except ValueError:
        raise ValueError(
            f"{path}: knob-table markers not found — the knob table must "
            f"live between {DOC_BEGIN.split(' ')[0]}… and {DOC_END}"
        ) from None
    return text[start:end]


def check_doc_sync(doc_path: str = "docs/running_guide.md") -> list:
    """Both-way registry<->doc check; returns a list of problem strings
    (empty = in sync). Every declared knob must appear in the doc table,
    and every YTK_* name in the table must be declared here."""
    # ytklint: allow(unseamed-io) reason=dev-time doc tooling on the checked-in markdown; not a runtime data path
    with open(doc_path, encoding="utf-8") as f:
        text = f.read()
    block = _doc_block(text, doc_path)
    documented = set(_NAME_RE.findall(block))
    declared = set(KNOBS)
    problems = []
    for name in sorted(declared - documented):
        problems.append(
            f"{doc_path}: knob {name} is declared in the registry but "
            "missing from the knob table (regen the table)"
        )
    for name in sorted(documented - declared):
        problems.append(
            f"{doc_path}: knob {name} appears in the knob table but is not "
            "declared in ytklearn_tpu/config/knobs.py"
        )
    if block.strip() != table_markdown().replace(DOC_END, "").strip():
        if not problems:
            problems.append(
                f"{doc_path}: knob table text drifted from the registry "
                "(regen the table)"
            )
    return problems


def sync_doc(doc_path: str = "docs/running_guide.md") -> bool:
    """Rewrite the doc's knob-table block from the registry. True = changed."""
    # ytklint: allow(unseamed-io) reason=dev-time doc tooling on the checked-in markdown; not a runtime data path
    with open(doc_path, encoding="utf-8") as f:
        text = f.read()
    _doc_block(text, doc_path)  # raises when markers are missing
    start = text.index(DOC_BEGIN)
    end = text.index(DOC_END) + len(DOC_END)
    new = text[:start] + table_markdown() + text[end:]
    if new == text:
        return False
    # ytklint: allow(unseamed-io) reason=dev-time doc tooling on the checked-in markdown; not a runtime data path
    with open(doc_path, "w", encoding="utf-8") as f:
        f.write(new)
    return True


def _main(argv) -> int:
    import sys

    if not argv or argv[0] not in ("table", "check", "regen"):
        sys.stderr.write(
            "usage: python -m ytklearn_tpu.config.knobs "
            "{table | check [doc] | regen [doc]}\n"
        )
        return 2
    cmd, rest = argv[0], argv[1:]
    doc = rest[0] if rest else "docs/running_guide.md"
    if cmd == "table":
        sys.stdout.write(table_markdown() + "\n")
        return 0
    if cmd == "regen":
        changed = sync_doc(doc)
        sys.stderr.write(f"{doc}: {'rewrote' if changed else 'unchanged'}\n")
        return 0
    problems = check_doc_sync(doc)
    for p in problems:
        sys.stderr.write(p + "\n")
    if problems:
        return 1
    sys.stderr.write(f"knob doc sync: OK ({len(KNOBS)} knobs)\n")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv[1:]))
