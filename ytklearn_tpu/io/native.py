"""ctypes bindings for the native C++ ingest parser (native/ytk_parse.cpp).

The .so is compiled on demand with g++ into native/build/, named by a hash
of its source and compile command, so a changed source or flag is a new
file and a binary from another tree is never picked up. A build or load
failure raises: the python parser is ~10x slower, and a training run that
silently fell back to it would not be the program the code claims to ship.
`YTK_NO_NATIVE=1` is the one explicit way onto the python parser (exact
drop-in: same rows, same errors, same first-seen feature-name order; parity
enforced by tests/test_native_ingest.py).

TPU-native framing: this is the runtime's data-loader component — the
reference parallelizes ingest across Java reader threads
(dataflow/DataFlow.java:483-534 readQueues + per-thread CoreData.readData);
here the same row-range parallelism is std::thread workers over one byte
buffer, feeding numpy columnar arrays that are a single device_put away
from the mesh.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from ..config import knobs

log = logging.getLogger(__name__)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "ytk_parse.cpp")
# no -march=native: the cached .so must run on whatever CPU the tree is
# copied to next
CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lock = threading.Lock()
_lib = None


def ensure_so(src: str, cmd: Sequence[str], stem: str) -> str:
    """Path of native/build/<stem>-<hash of source bytes + compile
    command>.so, compiled first if it is not there; raises
    CalledProcessError/OSError when the compile fails."""
    with open(src, "rb") as f:
        tag = hashlib.sha256(f.read() + " ".join(cmd).encode()).hexdigest()[:12]
    so = os.path.join(_REPO, "native", "build", f"{stem}-{tag}.so")
    if os.path.exists(so):
        return so
    os.makedirs(os.path.dirname(so), exist_ok=True)
    # per-process temp name: concurrent builders (multi-host JAX on one
    # machine, parallel pytest) each compile privately, then atomically
    # promote — last os.replace wins, never a torn .so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [*cmd, src, "-o", tmp], check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if knobs.get_bool("YTK_NO_NATIVE"):
            return None
        try:
            # ytklint: allow(blocking-call-under-lock) reason=first-touch build serialization is the point — every ingest thread must wait for the ONE compiler run instead of racing N compiles of the same .so
            so = ensure_so(_SRC, CXX, "libytkparse")
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                "native parser build failed (set YTK_NO_NATIVE=1 to opt "
                "into the ~10x slower python parser): "
                + e.stderr.decode(errors="replace")[:2000]
            ) from e
        lib = ctypes.CDLL(so)
        lib.ytk_parse.restype = ctypes.c_void_p
        lib.ytk_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64,
        ]
        for name in ("ytk_n_rows", "ytk_nnz", "ytk_n_label_vals",
                     "ytk_n_names", "ytk_name_bytes", "ytk_n_errors"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p]
        lib.ytk_fill.restype = None
        lib.ytk_fill.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 7
        lib.ytk_free.restype = None
        lib.ytk_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    """False only under YTK_NO_NATIVE=1; a broken toolchain raises."""
    return _load() is not None


@dataclass
class ParsedBlock:
    """Columnar parse result for a block of lines.

    Rows appear in input-line order. `labels` is ragged via label_ptr
    (1 entry for scalar losses, K for explicit multiclass vectors).
    `feat_ids` index into `names` (first-seen order across kept lines).
    """

    weights: np.ndarray  # (n,) f32
    label_ptr: np.ndarray  # (n+1,) i64
    labels: np.ndarray  # (L,) f32
    row_ptr: np.ndarray  # (n+1,) i64
    feat_ids: np.ndarray  # (nnz,) i32 -> names
    feat_vals: np.ndarray  # (nnz,) f32
    names: List[str]
    n_errors: int

    @property
    def n(self) -> int:
        return len(self.weights)


def parse_block(
    data: bytes,
    x_delim: str = "###",
    y_delim: str = ",",
    features_delim: str = ",",
    feature_name_val_delim: str = ":",
    n_threads: int = 0,
    divisor: int = 1,
    remainder: int = 0,
) -> ParsedBlock:
    """Parse a byte buffer of ytklearn-format lines natively.

    divisor/remainder implement the global line-modulo shard selection
    (fs.select_read_lines / reference IFileSystem.selectRead).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native parser unavailable")
    if len(y_delim) != 1 or len(features_delim) != 1 or len(feature_name_val_delim) != 1:
        raise ValueError("native parser requires single-char y/features/name-val delims")
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 1, 32)
    h = lib.ytk_parse(
        data, len(data), x_delim.encode(), y_delim.encode(),
        features_delim.encode(), feature_name_val_delim.encode(),
        n_threads, divisor, remainder,
    )
    try:
        n = lib.ytk_n_rows(h)
        nnz = lib.ytk_nnz(h)
        nlab = lib.ytk_n_label_vals(h)
        nnames = lib.ytk_n_names(h)
        nbytes = lib.ytk_name_bytes(h)
        weights = np.empty(n, np.float32)
        label_ptr = np.empty(n + 1, np.int64)
        labels = np.empty(nlab, np.float32)
        row_ptr = np.empty(n + 1, np.int64)
        feat_ids = np.empty(nnz, np.int32)
        feat_vals = np.empty(nnz, np.float32)
        name_buf = ctypes.create_string_buffer(max(int(nbytes), 1))
        lib.ytk_fill(
            h,
            weights.ctypes.data_as(ctypes.c_void_p),
            label_ptr.ctypes.data_as(ctypes.c_void_p),
            labels.ctypes.data_as(ctypes.c_void_p),
            row_ptr.ctypes.data_as(ctypes.c_void_p),
            feat_ids.ctypes.data_as(ctypes.c_void_p),
            feat_vals.ctypes.data_as(ctypes.c_void_p),
            ctypes.cast(name_buf, ctypes.c_void_p),
        )
        names = (
            name_buf.raw[: int(nbytes)].decode("utf-8").split("\n")[:-1]
            if nnames else []
        )
        return ParsedBlock(
            weights=weights, label_ptr=label_ptr, labels=labels,
            row_ptr=row_ptr, feat_ids=feat_ids, feat_vals=feat_vals,
            names=names, n_errors=int(lib.ytk_n_errors(h)),
        )
    finally:
        lib.ytk_free(h)


def parse_paths(
    fs,
    paths: Sequence[str],
    x_delim: str = "###",
    y_delim: str = ",",
    features_delim: str = ",",
    feature_name_val_delim: str = ":",
    n_threads: int = 0,
    divisor: int = 1,
    remainder: int = 0,
) -> ParsedBlock:
    """Parse files one at a time and merge the columnar outputs.

    Identical result to one parse_block call over the newline-normalized
    concatenation of all files in sorted-path order (same rows, errors,
    first-seen name order), but peak memory holds
    one file's raw bytes instead of the whole dataset (ADVICE r3: the
    reference ingest streams per reader thread, DataFlow.java:483-534).
    The line-modulo shard phase carries across file boundaries: every
    physical line counts, and each file is newline-normalized, so file k
    starts at global line sum(lines of files < k)."""
    from ..resilience import chaos_point, retry_call

    blocks: List[ParsedBlock] = []
    line0 = 0
    for p in sorted(fs.recur_get_paths(paths)):
        # same `io.read` retry/chaos seam as FileSystem.read_lines: a
        # transient fault rereads this one file, never kills the run
        def _read(path=p) -> bytes:
            chaos_point("io.read")
            with fs.open(path, "rb") as f:
                return f.read()

        b = retry_call(_read, site="io.read")
        if not b:
            continue
        if not b.endswith(b"\n"):
            b += b"\n"
        rem = (remainder - line0) % divisor if divisor > 1 else 0
        blocks.append(
            parse_block(
                b, x_delim, y_delim, features_delim, feature_name_val_delim,
                n_threads=n_threads, divisor=divisor, remainder=rem,
            )
        )
        line0 += b.count(b"\n")
        del b
    return merge_blocks(blocks)


def merge_blocks(blocks: Sequence[ParsedBlock]) -> ParsedBlock:
    """Concatenate ParsedBlocks row-wise, keeping the first-seen feature-name
    order across blocks (block order = file order = line order)."""
    if not blocks:
        return ParsedBlock(
            weights=np.empty(0, np.float32),
            label_ptr=np.zeros(1, np.int64),
            labels=np.empty(0, np.float32),
            row_ptr=np.zeros(1, np.int64),
            feat_ids=np.empty(0, np.int32),
            feat_vals=np.empty(0, np.float32),
            names=[], n_errors=0,
        )
    if len(blocks) == 1:
        return blocks[0]
    uniq: dict = {}
    remapped_ids: List[np.ndarray] = []
    for blk in blocks:
        remap = np.asarray(
            [uniq.setdefault(nm, len(uniq)) for nm in blk.names], np.int32
        )
        remapped_ids.append(
            remap[blk.feat_ids] if len(blk.names) else blk.feat_ids
        )
    label_ptr = [np.zeros(1, np.int64)]
    row_ptr = [np.zeros(1, np.int64)]
    loff = roff = 0
    for blk in blocks:
        label_ptr.append(blk.label_ptr[1:] + loff)
        row_ptr.append(blk.row_ptr[1:] + roff)
        loff += int(blk.label_ptr[-1])
        roff += int(blk.row_ptr[-1])
    return ParsedBlock(
        weights=np.concatenate([b.weights for b in blocks]),
        label_ptr=np.concatenate(label_ptr),
        labels=np.concatenate([b.labels for b in blocks]),
        row_ptr=np.concatenate(row_ptr),
        feat_ids=np.concatenate(remapped_ids),
        feat_vals=np.concatenate([b.feat_vals for b in blocks]),
        names=list(uniq),
        n_errors=sum(b.n_errors for b in blocks),
    )


def expand_labels_columnar(
    label_ptr: np.ndarray, labels: np.ndarray, n: int, K: int
):
    """Vectorized python-float() label expansion shared by the GBDT and
    convex fast paths: width-K vectors pass through; width-1 is an int()-
    truncated class index where a negative in-range value wraps (python
    list indexing) and anything outside [-K, K-1] is an error line.

    Returns (bad, y): bad (n,) bool error-row mask; y (n,) f32 for K==1
    (first label, extras ignored) or (n, K) f32 one-hot/verbatim, zero
    rows where bad."""
    firsts = labels[label_ptr[:-1]] if n else np.zeros(0, np.float32)
    if K == 1:
        return np.zeros(n, bool), firsts.astype(np.float32)
    widths = np.diff(label_ptr)
    bad = (widths != 1) & (widths != K)
    cls = np.trunc(firsts).astype(np.int64)
    is_cls = widths == 1
    bad |= is_cls & ((cls >= K) | (cls < -K))
    y = np.zeros((n, K), np.float32)
    fullm = ~bad & (widths == K)
    if fullm.any():
        src = label_ptr[:-1][fullm][:, None] + np.arange(K)
        y[fullm] = labels[src]
    onem = ~bad & is_cls
    if onem.any():
        ck = cls[onem]
        ck = np.where(ck < 0, ck + K, ck)
        y[np.where(onem)[0], ck] = 1.0
    return bad, y


def supports_delims(delim) -> bool:
    """The C parser handles multi-char x_delim but single-char y/features/
    name-val delims; other configs use the python path."""
    return (
        len(delim.x_delim) >= 1
        and len(delim.y_delim) == 1
        and len(delim.features_delim) == 1
        and len(delim.feature_name_val_delim) == 1
    )
