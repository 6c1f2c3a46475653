"""Command-line entry points — the bin/ layer.

Rebuild of reference bin/local_optimizer.sh:38-47 (model name + config +
optional py-transform, one local worker), predictor/Predicts.java:36-54
(offline batch predict CLI) and utils/LibsvmConvertTool.java:43 (format
converter). One host process drives the whole device mesh, so the
CommMaster rendezvous / per-slave JVM machinery has no equivalent: the
mesh is discovered from jax.devices() (or jax.distributed for
multi-host) instead of a TCP master.

Console scripts (pyproject.toml):
  ytklearn-tpu-train   <model_name> <config_path> [options]
  ytklearn-tpu-retrain <model_name> <config_path> [options]
  ytklearn-tpu-predict <config_path> <model_name> <file_dir> [options]
  ytklearn-tpu-serve   <config_path> <model_name> [options]
plus `python -m ytklearn_tpu.cli {train,retrain,predict,convert,serve} ...`.

`serve` and `retrain` have no reference counterpart (the reference stops
at the thread-safe OnlinePredictor library): `serve` fronts that API with
the compiled-scorer + micro-batching online layer (docs/serving.md), and
`retrain` is the continuous-training driver feeding its hot-reload
registry (docs/continual.md).
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
from typing import List, Optional

MODEL_NAMES = (
    "linear",
    "multiclass_linear",
    "fm",
    "ffm",
    "gbmlr",
    "gbsdt",
    "gbhmlr",
    "gbhsdt",
    "gbdt",
)
GBST_NAMES = ("gbmlr", "gbsdt", "gbhmlr", "gbhsdt")


def _setup_logging(verbose: bool) -> None:
    logging.basicConfig(
        level=logging.DEBUG if verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
        stream=sys.stderr,
    )


def _apply_overrides(cfg: dict, sets: List[str]) -> dict:
    """--set key=value overrides (reference: TrainWorker.setCustomParam ->
    config.withValue, worker/TrainWorker.java:118-131). Values parse as
    JSON when possible, else stay strings."""
    from .config import hocon

    for kv in sets or []:
        key, sep, val = kv.partition("=")
        if not sep:
            raise SystemExit(f"--set expects key=value, got {kv!r}")
        try:
            parsed = json.loads(val)
        except json.JSONDecodeError:
            parsed = val
        cfg = hocon.set_path(cfg, key.strip(), parsed)
    return cfg


def _make_mesh(n_devices: Optional[int]):
    import jax

    from .parallel.mesh import make_mesh

    avail = len(jax.devices())
    n = n_devices if n_devices and n_devices > 0 else avail
    if n > avail:
        raise SystemExit(f"requested {n} devices, only {avail} available")
    return make_mesh(n) if n > 1 else None


def _load_hook(need: bool, script: str):
    if not need:
        return None
    from .io.reader import load_transform_hook

    return load_transform_hook(script)


def _setup_trace(trace_out: str) -> None:
    """--trace-out: enable obs + register the Chrome-trace export."""
    if trace_out:
        from . import obs

        obs.configure(enabled=True, trace_path=trace_out)


def _flush_trace(trace_out: str) -> None:
    """Write the trace now — *_main may be driven in-process (no atexit)."""
    if trace_out:
        from . import obs

        obs.flush()


def _setup_compile_cache() -> None:
    """Place JAX's persistent compile cache before the first compile."""
    from .compile_cache import configure_compile_cache

    logging.getLogger("ytklearn_tpu.cli").info(
        "compile cache: %s", configure_compile_cache()
    )


def _setup_profile(profile: Optional[str]) -> None:
    """--profile [DIR]: arm the ytkprof profiling plane (phase accounting,
    compile ledger, memory-watermark sampler); with DIR, also capture
    jax.profiler traces per phase into it (YTK_PROF everywhere else)."""
    if profile is None:
        return
    from .obs import profiler

    if profile:
        profiler.configure_profiler(on=True, capture_dir=profile)
    else:
        profiler.configure_profiler(on=True)


def train_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ytklearn-tpu-train",
        description="Train any ytk-learn model family on the TPU mesh "
        "(reference: bin/local_optimizer.sh + LocalTrainWorker)",
    )
    ap.add_argument("model_name", choices=MODEL_NAMES)
    ap.add_argument("config_path")
    ap.add_argument("--transform", action="store_true", help="enable the python line-transform hook")
    ap.add_argument("--transform-script", default="bin/transform.py")
    ap.add_argument("--devices", type=int, default=0, help="mesh size (default: all local devices)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="on failure, retry with model.continue_train=true to "
                    "resume from the last checkpoint dump (reference: the "
                    "bin/hadoop_optimizer.sh:53-80 restart loop)")
    ap.add_argument("--resume", default="never", choices=("never", "auto"),
                    help="auto: when a complete checkpoint already exists at "
                    "model.data_path, re-enter training from it "
                    "(model.continue_train=true) — the relaunch half of the "
                    "preemption contract: a SIGTERM'd run dumps an emergency "
                    "checkpoint at its next round/iteration boundary and "
                    "exits 143 (docs/fault_tolerance.md)")
    ap.add_argument("--coordinator", default="",
                    help="host:port of the jax.distributed coordinator — the "
                    "CommMaster equivalent; use with --num-processes/"
                    "--process-id for multi-host training")
    ap.add_argument("--num-processes", type=int, default=0)
    ap.add_argument("--process-id", type=int, default=-1)
    ap.add_argument("--set", action="append", dest="sets", metavar="KEY=VALUE",
                    help="config override, repeatable")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run to "
                    "this path (YTK_TRACE=path everywhere else; see "
                    "docs/observability.md)")
    ap.add_argument("--profile", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="arm the ytkprof profiling plane: phase/device-time "
                    "accounting, compile ledger, memory watermarks; with DIR "
                    "also capture jax.profiler traces into it (YTK_PROF "
                    "everywhere else; see docs/observability.md)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    _setup_logging(args.verbose)
    _setup_trace(args.trace_out)
    _setup_profile(args.profile)
    _setup_compile_cache()

    # multi-host rendezvous BEFORE any backend touch (the CommMaster
    # equivalent; reference: bin/cluster_optimizer.sh slave fan-out).
    # Without --coordinator this is a no-op unless YTKLEARN_TPU_DISTRIBUTED=1
    # asks for pod auto-detection; unset world params stay None so jax
    # auto-detects topology.
    from .parallel.mesh import distributed_initialize_if_needed

    kw = {}
    if args.coordinator:
        kw["coordinator_address"] = args.coordinator
        if args.num_processes > 0:
            kw["num_processes"] = args.num_processes
        if args.process_id >= 0:
            kw["process_id"] = args.process_id
    distributed_initialize_if_needed(**kw)

    from .config import hocon

    cfg = _apply_overrides(hocon.load(args.config_path), args.sets)
    mesh = _make_mesh(args.devices)
    hook = _load_hook(args.transform, args.transform_script)
    name = args.model_name

    log = logging.getLogger("ytklearn_tpu.cli")
    if args.resume == "auto":
        # atomic dumps (fs.atomic_open) mean model.data_path either holds
        # the newest COMPLETE checkpoint or nothing — no torn-file triage
        from .io.fs import create_filesystem as _mkfs

        _fs = _mkfs(str(cfg.get("fs_scheme", "local")))
        _mpath = hocon.get_path(cfg, "model.data_path")
        if _mpath and _fs.exists(str(_mpath)):
            cfg = hocon.set_path(cfg, "model.continue_train", True)
            log.info("--resume auto: checkpoint found at %s; resuming", _mpath)
        else:
            log.info(
                "--resume auto: no checkpoint at %s; cold start", _mpath
            )
    restarts = max(args.max_restarts, 0)
    import jax as _jax

    if restarts and _jax.process_count() > 1:
        # a single rank re-entering training would desynchronize the
        # group's collectives; multi-process recovery = restart the whole
        # launcher with continue_train (the reference's model too:
        # bin/hadoop_optimizer.sh restarts the entire job)
        log.warning(
            "--max-restarts is per-process and unsafe in multi-process "
            "mode; disabled — restart the launcher to resume from the "
            "last checkpoint"
        )
        restarts = 0
    from . import obs
    from .resilience import Preempted

    for attempt in range(restarts + 1):
        try:
            # the run's root span, around the data load too (the trainers
            # ask for the same root and find it open)
            with obs.root_span("train.run", family=name):
                rc = _train_once(name, cfg, mesh, hook)
            _flush_trace(args.trace_out)
            return rc
        except Preempted as e:
            # not a failure: the emergency checkpoint is on disk and the
            # restart loop must NOT eat the grace period re-entering
            # training — exit with the signal's conventional status so
            # the scheduler relaunches (with --resume auto) instead
            log.warning("%s; exiting %d", e, e.exit_code)
            _flush_trace(args.trace_out)
            return e.exit_code
        except KeyboardInterrupt:
            raise
        except Exception:
            if attempt >= restarts:
                raise
            log.exception(
                "training attempt %d/%d failed; restarting with "
                "model.continue_train=true",
                attempt + 1, restarts + 1,
            )
            # resume from the last periodic dump (fail-fast + restart is the
            # reference's recovery model: checkpoint-as-model + relaunch)
            cfg = hocon.set_path(cfg, "model.continue_train", True)
    return 1  # unreachable


def _train_once(name: str, cfg: dict, mesh, hook) -> int:
    from .io.fs import create_filesystem

    fs = create_filesystem(str(cfg.get("fs_scheme", "local")))
    if name == "gbdt":
        from .config.params import GBDTParams
        from .gbdt.data import GBDTIngest
        from .gbdt.trainer import GBDTTrainer

        p = GBDTParams.from_config(cfg)
        ingest = GBDTIngest(p, fs=fs, transform_hook=hook)
        train, test = ingest.load()
        res = GBDTTrainer(p, mesh=mesh, fs=fs).train(train=train, test=test)
        print(json.dumps({
            "model": name,
            "trees": len(res.model.trees),
            "train_loss": res.train_loss,
            "test_loss": res.test_loss,
            "train_metrics": res.train_metrics,
            "test_metrics": res.test_metrics,
        }))
        return 0

    from .config.params import CommonParams

    p = CommonParams.from_config(cfg)
    if name in GBST_NAMES:
        from .boost import GBSTTrainer
        from .io.reader import DataIngest

        ingest = DataIngest(p, fs=fs, transform_hook=hook).load()
        res = GBSTTrainer(p, name, mesh=mesh, fs=fs).train(ingest=ingest)
        print(json.dumps({
            "model": name,
            "trees": res.n_trees,
            "train_loss": res.train_loss,
            "test_loss": res.test_loss,
            "train_metrics": res.train_metrics,
            "test_metrics": res.test_metrics,
        }))
        return 0

    from .train import HoagTrainer

    res = HoagTrainer(p, name, mesh=mesh, fs=fs, transform_hook=hook).train()
    print(json.dumps({
        "model": name,
        "n_iter": res.n_iter,
        "status": res.status,
        "avg_loss": res.avg_loss,
        "test_loss": res.test_loss,
        "train_metrics": res.train_metrics,
        "test_metrics": res.test_metrics,
    }))
    return 0


def predict_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ytklearn-tpu-predict",
        description="Offline batch prediction "
        "(reference: bin/predict.sh + predictor/Predicts.java:36-54)",
    )
    ap.add_argument("config_path")
    ap.add_argument("model_name", choices=MODEL_NAMES)
    ap.add_argument("file_dir", help="file or directory of data to predict")
    ap.add_argument("--transform", action="store_true")
    ap.add_argument("--transform-script", default="bin/transform.py")
    ap.add_argument("--save-mode", default="predict_result_only",
                    choices=("predict_result_only", "label_and_predict", "predict_as_feature"))
    ap.add_argument("--suffix", default="_predict")
    ap.add_argument("--max-error-tol", type=int, default=100)
    ap.add_argument("--eval-metric", default="", help='e.g. "auc,mae"')
    ap.add_argument("--predict-type", default="value", choices=("value", "leafid"))
    ap.add_argument("--set", action="append", dest="sets", metavar="KEY=VALUE")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the batch "
                    "predict to this path")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    _setup_logging(args.verbose)
    _setup_trace(args.trace_out)
    _setup_compile_cache()

    from .config import hocon
    from .predict import batch_predict_from_files, create_predictor

    cfg = _apply_overrides(hocon.load(args.config_path), args.sets)
    predictor = create_predictor(args.model_name, cfg)
    K = int(cfg.get("k", -1)) if args.model_name == "multiclass_linear" else -1
    avg_loss = batch_predict_from_files(
        predictor,
        args.model_name,
        args.file_dir,
        need_py_transform=args.transform,
        py_transform_script=args.transform_script,
        result_save_mode=args.save_mode,
        result_file_suffix=args.suffix,
        max_error_tol=args.max_error_tol,
        eval_metric_str=args.eval_metric,
        predict_type_str=args.predict_type,
        K=K,
    )
    _flush_trace(args.trace_out)
    print(json.dumps({"model": args.model_name, "avg_loss": avg_loss}))
    return 0


def convert_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ytklearn-tpu-convert",
        description="libsvm -> ytklearn format "
        "(reference: bin/libsvm_convert_2_ytklearn.sh + utils/LibsvmConvertTool.java)",
    )
    ap.add_argument("mode", help='binary_classification@l0,l1 | '
                                 'multi_classification@l0,l1,... | regression')
    ap.add_argument("input_path")
    ap.add_argument("output_path")
    ap.add_argument("--x-delim", default="###")
    ap.add_argument("--y-delim", default=",")
    ap.add_argument("--features-delim", default=",")
    ap.add_argument("--feature-name-val-delim", default=":")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    _setup_logging(args.verbose)

    from .io.libsvm import convert_libsvm

    cnt = convert_libsvm(
        args.mode,
        args.input_path,
        args.output_path,
        x_delim=args.x_delim,
        y_delim=args.y_delim,
        features_delim=args.features_delim,
        feature_name_val_delim=args.feature_name_val_delim,
    )
    print(json.dumps({"lines": cnt, "output": args.output_path}))
    return 0


def retrain_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ytklearn-tpu-retrain",
        description="Continuous training driver: warm-start a candidate on "
        "new data in a shadow path, validate it against the health gates "
        "and a held-out metric band versus the serving incumbent, and "
        "atomically promote only on pass — the serving registry's "
        "fingerprint watcher hot-swaps the promoted model under traffic "
        "(docs/continual.md)",
    )
    ap.add_argument("model_name", choices=MODEL_NAMES)
    ap.add_argument("config_path")
    ap.add_argument("--data", default="",
                    help="fresh training data path(s) (comma-separated); "
                    "overrides data.train.data_path")
    ap.add_argument("--test", default="",
                    help="held-out data path(s) for the metric gate; "
                    "overrides data.test.data_path")
    ap.add_argument("--mode", default="", choices=("", "warm", "ftrl"),
                    help="warm = full warm-start refit (default); ftrl = "
                    "one FTRL-proximal online pass (convex families)")
    ap.add_argument("--extra-rounds", type=int, default=-1,
                    help="extra boosting rounds for GBDT/GBST warm starts "
                    "(default: continual.extra_rounds)")
    ap.add_argument("--rollback", action="store_true",
                    help="restore the newest archived version over the "
                    "served path instead of retraining")
    ap.add_argument("--transform", action="store_true",
                    help="enable the python line-transform hook")
    ap.add_argument("--transform-script", default="bin/transform.py")
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size (default: all local devices)")
    ap.add_argument("--set", action="append", dest="sets", metavar="KEY=VALUE",
                    help="config override, repeatable")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the retrain")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    _setup_logging(args.verbose)
    _setup_trace(args.trace_out)
    _setup_compile_cache()

    from .config import hocon
    from .continual import RetrainRejected, retrain, rollback

    cfg = _apply_overrides(hocon.load(args.config_path), args.sets)
    if args.data:
        cfg = hocon.set_path(cfg, "data.train.data_path", args.data)
    if args.test:
        cfg = hocon.set_path(cfg, "data.test.data_path", args.test)

    if args.rollback:
        res = rollback(args.model_name, cfg)
        _flush_trace(args.trace_out)
        print(json.dumps(res.to_json()))
        return 0

    mesh = _make_mesh(args.devices)
    hook = _load_hook(args.transform, args.transform_script)
    from .resilience import Preempted

    try:
        res = retrain(
            args.model_name, cfg, mesh=mesh,
            mode=args.mode or None,
            extra_rounds=args.extra_rounds if args.extra_rounds >= 0 else None,
            transform_hook=hook,
        )
    except Preempted as e:
        # candidate training was preempted; the incumbent keeps serving,
        # the lock is released, and the next cron tick simply retrains
        logging.getLogger("ytklearn_tpu.cli").warning("%s; exiting %d", e, e.exit_code)
        _flush_trace(args.trace_out)
        return e.exit_code
    except RetrainRejected as e:
        # YTK_CONTINUAL_STRICT=1: a rejection is a hard failure for the
        # surrounding pipeline, but still a clean JSON record on stdout
        print(json.dumps({
            "promoted": False,
            "strict": True,
            "reasons": e.report.reasons,
        }))
        _flush_trace(args.trace_out)
        return 1
    _flush_trace(args.trace_out)
    print(json.dumps(res.to_json()))
    return 0


def serve_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ytklearn-tpu-serve",
        description="Online prediction server: compiled batch scorer with a "
        "padded shape ladder, dynamic micro-batching with backpressure, and "
        "fingerprint-watch hot model reload (docs/serving.md)",
    )
    ap.add_argument("config_path")
    ap.add_argument("model_name", choices=MODEL_NAMES)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8080,
                    help="listen port (0 picks an ephemeral port)")
    ap.add_argument("--name", default="default",
                    help="registry name for this model (the default target "
                    "of /predict requests without a \"model\" field)")
    ap.add_argument("--extra-model", action="append", default=[],
                    metavar="NAME:MODEL_NAME:CONFIG_PATH",
                    help="load an additional model into the registry "
                    "(repeatable) — multi-model serving from one process; "
                    "requests address it via the \"model\" field. In fleet "
                    "mode every replica loads every model")
    ap.add_argument("--ladder", default="",
                    help='compiled batch-shape ladder, e.g. "1,8,64,512" '
                    "(default; env YTK_SERVE_LADDER). Every rung compiles "
                    "once at load, so steady-state traffic never retraces")
    ap.add_argument("--max-batch", type=int, default=512,
                    help="max rows coalesced into one scorer call")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch straggler wait after the first request")
    ap.add_argument("--max-queue", type=int, default=2048,
                    help="pending-request bound; beyond it requests are shed "
                    "with a typed 429 instead of queueing unboundedly")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="default per-request deadline (0 = none); expired "
                    "requests fail with 504 before wasting scorer time")
    ap.add_argument("--watch-interval", type=float, default=None,
                    help="model-file fingerprint poll seconds for hot reload "
                    "(default 5; 0 disables; env YTK_SERVE_WATCH_S)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="serving fleet size: N spawns N replica worker "
                    "processes behind a shared-nothing front (-1 = one per "
                    "two CPU cores; 0 = single-process; CPU hosts only — a "
                    "chip belongs to one process; env YTK_SERVE_REPLICAS — "
                    "see docs/serving.md)")
    ap.add_argument("--replicas-min", type=int, default=None,
                    help="fleet autoscaler floor: minimum replica slots "
                    "(default: --replicas; env YTK_SERVE_REPLICAS_MIN — "
                    "see docs/serving.md autoscaling)")
    ap.add_argument("--replicas-max", type=int, default=None,
                    help="fleet autoscaler ceiling: maximum replica slots "
                    "(default: --replicas, which disarms autoscaling; env "
                    "YTK_SERVE_REPLICAS_MAX). A band wider than one value "
                    "arms the load-driven autoscaler: the front grows or "
                    "drain-reaps replicas within [min, max] from backlog/"
                    "shed/p99 signals")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="p99 latency SLO in ms for the AIMD batch-size "
                    "controller (0 disables AIMD and restores the fixed "
                    "--max-batch/--max-wait-ms; env YTK_SERVE_SLO_MS, "
                    "default 100)")
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="bounded LRU prediction-cache rows, keyed on "
                    "(model fingerprint, feature row); 0 disables (env "
                    "YTK_SERVE_CACHE_ROWS)")
    ap.add_argument("--replica-id", type=int, default=None,
                    help="fleet-internal: this process is replica N (set by "
                    "the front; stamps obs identity for postmortems)")
    ap.add_argument("--set", action="append", dest="sets", metavar="KEY=VALUE",
                    help="config override, repeatable")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON at shutdown")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    _setup_logging(args.verbose)
    _setup_trace(args.trace_out)
    _setup_compile_cache()

    from .config import knobs

    replicas = (args.replicas if args.replicas is not None
                else knobs.get_int("YTK_SERVE_REPLICAS"))
    slo_ms = (args.slo_ms if args.slo_ms is not None
              else knobs.get_float("YTK_SERVE_SLO_MS"))
    cache_rows = (args.cache_rows if args.cache_rows is not None
                  else knobs.get_int("YTK_SERVE_CACHE_ROWS"))
    # autoscaling band (0 / unset = follow --replicas = fixed fleet); a
    # band alone is enough to go fleet mode: `--replicas-max 4` on a
    # default single-process invocation serves one replica that can grow
    r_min = (args.replicas_min if args.replicas_min is not None
             else knobs.get_int("YTK_SERVE_REPLICAS_MIN")) or 0
    r_max = (args.replicas_max if args.replicas_max is not None
             else knobs.get_int("YTK_SERVE_REPLICAS_MAX")) or 0

    if replicas != 0 or r_max > 0 or r_min > 0:
        return _serve_fleet_main(args, replicas, slo_ms, cache_rows,
                                 r_min, r_max)

    from .config import hocon
    from . import obs
    from .serve import BatchPolicy, ModelRegistry, ServeApp, parse_ladder

    if args.replica_id is not None:
        # fleet worker: every obs event / flight dump / metrics scrape
        # from this process names its replica
        obs.set_identity(replica_id=args.replica_id)

    cfg = _apply_overrides(hocon.load(args.config_path), args.sets)
    ladder = parse_ladder(args.ladder) if args.ladder else None
    registry = ModelRegistry(ladder=ladder, watch_interval_s=args.watch_interval)
    registry.load(args.name, args.model_name, cfg)
    for spec in args.extra_model:
        try:
            xname, xmodel, xconf = spec.split(":", 2)
        except ValueError:
            ap.error(f"--extra-model {spec!r}: expected "
                     "NAME:MODEL_NAME:CONFIG_PATH")
        if xmodel not in MODEL_NAMES:
            ap.error(f"--extra-model {spec!r}: unknown model family "
                     f"{xmodel!r} (choices: {', '.join(MODEL_NAMES)})")
        registry.load(xname, xmodel,
                      _apply_overrides(hocon.load(xconf), args.sets))
    registry.start_watching()
    policy = BatchPolicy(
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue,
        default_deadline_ms=args.deadline_ms,
    )
    app = ServeApp(
        registry, policy, host=args.host, port=args.port,
        slo_ms=slo_ms, cache_rows=cache_rows, replica_id=args.replica_id,
    ).start()
    app.install_signal_handlers()
    print(json.dumps({
        "serving": args.name,
        "model": args.model_name,
        "host": args.host,
        "port": app.port,
        "replica_id": args.replica_id,
        "ladder": list(registry.get(args.name).scorer.ladder),
        # monotonic-offset handshake: this process's obs clock origin on
        # the wall clock — the fleet front stamps it on the replica handle
        # so cross-process trace merges stay aligned (obs/trace.py)
        "wall_t0": obs.core.WALL_T0,
    }), flush=True)
    try:
        while app._serve_thread is not None and app._serve_thread.is_alive():
            app._serve_thread.join(timeout=1.0)
    except KeyboardInterrupt:
        app.stop(drain=True)
    _flush_trace(args.trace_out)
    return 0


def _serve_fleet_main(args, replicas: int, slo_ms, cache_rows,
                      r_min: int = 0, r_max: int = 0) -> int:
    """`serve --replicas N`: front process owning N worker subprocesses."""
    from .serve import (
        BatchPolicy,
        FleetFront,
        default_replica_count,
        serve_worker_argv,
    )

    if replicas < 0:
        replicas = default_replica_count()
    if replicas == 0:
        # reached via a bare autoscaling band (--replicas-max without
        # --replicas): start at the floor and let load grow the fleet
        replicas = max(1, r_min)
    worker_flags = []
    for flag, val in (
        ("--name", args.name),
        ("--ladder", args.ladder),
        ("--max-batch", args.max_batch),
        ("--max-wait-ms", args.max_wait_ms),
        ("--max-queue", args.max_queue),
        ("--deadline-ms", args.deadline_ms),
        ("--watch-interval", args.watch_interval),
        ("--slo-ms", slo_ms),
        ("--cache-rows", cache_rows),
    ):
        if val not in (None, ""):
            worker_flags += [flag, str(val)]
    for s in args.sets or []:
        worker_flags += ["--set", s]
    for spec in getattr(args, "extra_model", None) or []:
        # every replica serves the full model set (shared-nothing fleet:
        # any replica can answer any named-model request)
        worker_flags += ["--extra-model", spec]
    if args.verbose:
        worker_flags.append("--verbose")
    front = FleetFront(
        serve_worker_argv(args.config_path, args.model_name, worker_flags),
        replicas,
        policy=BatchPolicy(
            max_batch=args.max_batch,
            max_wait_ms=min(args.max_wait_ms, 1.0),
            max_queue=args.max_queue,
            default_deadline_ms=args.deadline_ms,
        ),
        host=args.host,
        port=args.port,
        slo_ms=slo_ms,
        replicas_min=(r_min or None),
        replicas_max=(r_max or None),
    )
    front.start().serve_http()
    front.install_signal_handlers()
    from . import obs

    print(json.dumps({
        "serving": args.name,
        "model": args.model_name,
        "host": args.host,
        "port": front.port,
        "replicas": front.n_replicas,
        "replicas_min": front.replicas_min,
        "replicas_max": front.replicas_max,
        "autoscale": front.autoscaler is not None,
        "fleet": True,
        "replica_ports": {
            str(rid): h.port for rid, h in sorted(front.handles.items())
        },
        "wall_t0": obs.core.WALL_T0,
    }), flush=True)
    try:
        while front._serve_thread is not None and front._serve_thread.is_alive():
            front._serve_thread.join(timeout=1.0)
    except KeyboardInterrupt:
        front.stop(drain=True)
    _flush_trace(args.trace_out)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m ytklearn_tpu.cli "
              "{train,retrain,predict,convert,serve} ...")
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "train":
        return train_main(rest)
    if cmd == "retrain":
        return retrain_main(rest)
    if cmd == "predict":
        return predict_main(rest)
    if cmd == "convert":
        return convert_main(rest)
    if cmd == "serve":
        return serve_main(rest)
    print(f"unknown command {cmd!r}; expected "
          "train|retrain|predict|convert|serve", file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
