"""Datasets: MNIST IDX parsing, sharded batching, synthetic workloads.

The reference ships gzipped IDX files and parses them in Go
(``DSML/client/client.go:270-350``). Its mirror is missing the 60k-image
training blob (``/root/reference/.MISSING_LARGE_BLOBS``, SURVEY.md §8.11), so
:func:`load_mnist` transparently falls back to carving a train/test split out
of the 10k test set (and can augment it with pixel shifts to recover headroom)
— real train images are used automatically when present at
``data/mnist/train-images-idx3-ubyte.gz``.

Also provides :func:`synthetic_classification` (benchmark workloads never
bottlenecked on disk) and :func:`shard_batches`, the host-side data-parallel
batch iterator (per-device shards laid out for a ``dp`` mesh axis).
"""

from __future__ import annotations

import gzip
import hashlib
import os
import struct
from dataclasses import dataclass

import numpy as np

from dsml_tpu.utils.logging import get_logger

log = get_logger("data")

_IMAGES_MAGIC = 2051
_LABELS_MAGIC = 2049


def _read_idx(path: str) -> np.ndarray:
    """Parse one (gzipped) IDX file (images or labels). Decoding goes through
    the native C++ runtime when built (dsml_tpu/runtime/native), with a pure
    numpy fallback."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        blob = f.read()
    try:
        from dsml_tpu.runtime import native

        if native.available():
            data, _ = native.idx_parse(blob)
            return data
    except Exception as e:  # noqa: BLE001 — any native hiccup falls back
        log.warning("native IDX parse failed (%s); numpy fallback", e)
    magic, count = struct.unpack(">II", blob[:8])
    if magic == _IMAGES_MAGIC:
        rows, cols = struct.unpack(">II", blob[8:16])
        return np.frombuffer(blob, np.uint8, count * rows * cols, 16).reshape(count, rows, cols)
    if magic == _LABELS_MAGIC:
        return np.frombuffer(blob, np.uint8, count, 8)
    raise ValueError(f"{path}: unknown IDX magic {magic}")


@dataclass
class Dataset:
    train_x: np.ndarray  # [N, ...] float32 in [0, 1]
    train_y: np.ndarray  # [N] int32
    test_x: np.ndarray
    test_y: np.ndarray

    @property
    def n_train(self) -> int:
        return self.train_x.shape[0]


def load_mnist(
    data_dir: str = "data/mnist",
    flatten: bool = True,
    augment_fallback: bool = True,
    holdout: int = 2000,
) -> Dataset:
    """Load MNIST; fall back to a t10k-derived split when the 60k train
    images are absent (see module docstring)."""
    train_images = os.path.join(data_dir, "train-images-idx3-ubyte.gz")
    test_x = _read_idx(os.path.join(data_dir, "t10k-images-idx3-ubyte.gz"))
    test_y = _read_idx(os.path.join(data_dir, "t10k-labels-idx1-ubyte.gz"))
    if os.path.exists(train_images):
        train_x = _read_idx(train_images)
        train_y = _read_idx(os.path.join(data_dir, "train-labels-idx1-ubyte.gz"))
    else:
        log.warning(
            "train-images blob absent (stripped from the reference mirror); "
            "splitting t10k %d/%d train/test%s",
            test_x.shape[0] - holdout, holdout, " with shift augmentation" if augment_fallback else "",
        )
        train_x, train_y = test_x[:-holdout], test_y[:-holdout]
        test_x, test_y = test_x[-holdout:], test_y[-holdout:]
        if augment_fallback:
            train_x, train_y = _augment_shifts(train_x, train_y)

    def prep(x):
        x = x.astype(np.float32) / 255.0
        return x.reshape(x.shape[0], -1) if flatten else x[..., None]

    return Dataset(prep(train_x), train_y.astype(np.int32), prep(test_x), test_y.astype(np.int32))


def _augment_shifts(x: np.ndarray, y: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """5× the data with ±1-pixel translations (cheap, label-preserving)."""
    shifted = [x]
    for dy, dx in ((1, 0), (-1, 0), (0, 1), (0, -1)):
        s = np.roll(x, (dy, dx), axis=(1, 2))
        # zero the wrapped edge
        if dy == 1:
            s[:, 0, :] = 0
        elif dy == -1:
            s[:, -1, :] = 0
        if dx == 1:
            s[:, :, 0] = 0
        elif dx == -1:
            s[:, :, -1] = 0
        shifted.append(s)
    return np.concatenate(shifted), np.tile(y, len(shifted))


def synthetic_classification(
    n: int, features: int, classes: int = 10, seed: int = 0, image_shape: tuple | None = None
) -> Dataset:
    """Linearly-separable-ish synthetic data; loss must drop fast on it, which
    makes it the convergence canary for trainer tests and benchmarks."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((classes, features)).astype(np.float32) * 2.0
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = centers[y] + rng.standard_normal((n, features)).astype(np.float32)
    if image_shape is not None:
        x = x.reshape(n, *image_shape)
    split = max(1, int(n * 0.9))
    return Dataset(x[:split], y[:split], x[split:], y[split:])


def prefetch_batches(iterator, depth: int = 2):
    """Run ``iterator`` in a background thread, keeping up to ``depth``
    batches ready — host-side batch assembly (shuffle-gather, the pure-numpy
    cost of :func:`shard_batches`) overlaps device compute instead of
    serializing with it. The reference's client assembled batches inline on
    the training thread (``client.go:592-603``)."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    _END = object()

    def put(item) -> bool:
        # never block forever: if the consumer abandoned the generator
        # (exception mid-epoch), the worker must exit, not pin the thread
        # and `depth` batches of host memory
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in iterator:
                if not put(item):
                    return
            put(_END)
        except BaseException as e:  # noqa: BLE001 — surface on the consumer side
            put(e)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


def shard_batches(
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int,
    seed: int | None = None,
    drop_remainder: bool = True,
    native: bool | None = None,
):
    """Yield (x_batch, y_batch) host batches, shuffled per epoch. The batch is
    the GLOBAL batch; the mesh sharding (``P('dp')`` on axis 0) splits it
    across data-parallel ranks at dispatch — the real data sharding the
    reference lacked (its 'DP' shipped identical full batches everywhere,
    SURVEY.md §2.3).

    ``native`` routes the (large) x-row gather through the C++
    background-thread loader (``runtime.native.NativePrefetcher``) so it
    overlaps device compute at the native layer; ``None`` auto-detects,
    ``False`` forces the numpy path. Values are identical either way
    (tests pin it) — labels stay a numpy gather (tiny)."""
    n = x.shape[0]
    idx = np.arange(n)
    if seed is not None:
        np.random.default_rng(seed).shuffle(idx)
    end = (n // batch_size) * batch_size if drop_remainder else n
    n_full = end // batch_size
    # SETUP only inside the try: once batches start yielding, a native
    # error must propagate — falling back mid-stream would restart the
    # epoch from batch 0 and silently feed duplicated data
    pf = batch_idx = None
    if native is not False and n_full > 0:
        try:
            from dsml_tpu.runtime import native as nat

            if nat.available():
                batch_idx = idx[: n_full * batch_size].reshape(
                    n_full, batch_size
                ).astype(np.int32)
                pf = nat.NativePrefetcher(x, batch_idx, depth=2)
        except Exception:
            if native:  # explicitly requested — don't silently degrade
                raise
            pf = None
    if native and pf is None:
        raise RuntimeError(
            "native=True but the native runtime is unavailable (no compiler?)"
        )
    if pf is not None:
        for b, xb in enumerate(pf):
            yield xb, y[batch_idx[b]]
        if not drop_remainder and end > n_full * batch_size:
            sel = idx[n_full * batch_size : end]
            yield x[sel], y[sel]
        return
    for start in range(0, end, batch_size):
        sel = idx[start : start + batch_size]
        yield x[sel], y[sel]


def lm_window_batches(
    tokens: np.ndarray,
    seq_len: int,
    batch_size: int,
    seed: int = 0,
    steps: int | None = None,
):
    """Yield (x, y) next-token LM batches: ``batch_size`` random windows of
    ``seq_len`` tokens each, y = x shifted one token left. The language-model
    counterpart of :func:`shard_batches` (same contract: GLOBAL batch, the
    mesh's ``P('dp')`` placement shards it); composes with
    :func:`prefetch_batches` so window assembly overlaps device compute.
    ``steps=None`` streams forever (training loops bound their own step
    count)."""
    tokens = np.asarray(tokens)
    if len(tokens) < seq_len + 1:
        raise ValueError(f"corpus of {len(tokens)} tokens too small for seq_len={seq_len}")
    rng = np.random.default_rng(seed)
    produced = 0
    while steps is None or produced < steps:
        # a start s is valid iff s + seq_len + 1 <= len (y reaches one past
        # x), so the exclusive high is len - seq_len — the last token of the
        # corpus IS reachable as a target
        starts = rng.integers(0, len(tokens) - seq_len, size=batch_size)
        x = np.stack([tokens[s : s + seq_len] for s in starts])
        y = np.stack([tokens[s + 1 : s + seq_len + 1] for s in starts])
        yield x.astype(np.int32), y.astype(np.int32)
        produced += 1


def carve_lm_eval_split(
    tokens: np.ndarray, seq_len: int, batch_size: int, frac: float = 0.05
) -> tuple[np.ndarray, np.ndarray | None]:
    """Split a token stream into (train, eval) tails for held-out perplexity.
    Returns ``(tokens, None)`` — eval disabled — when the corpus is too small
    to carve ``frac`` (or one batch of windows) without starving training."""
    tokens = np.asarray(tokens)
    carve = max((seq_len + 1) * batch_size, int(len(tokens) * frac), seq_len + 2)
    if carve > len(tokens) // 4 or len(tokens) - carve <= seq_len + 1:
        return tokens, None
    split = len(tokens) - carve
    return tokens[:split], tokens[split:]


# text-rich stdlib + dependency modules whose docstrings form the on-disk
# English prose pool for build_prose_corpus (importing any of these is
# side-effect free; missing ones are skipped)
_PROSE_MODULES = (
    "argparse", "ast", "asyncio", "calendar", "codecs", "collections",
    "concurrent.futures", "configparser", "contextlib", "csv", "datetime",
    "decimal", "difflib", "dis", "doctest", "email", "enum", "fractions",
    "functools", "gettext", "heapq", "html", "http", "imaplib", "inspect",
    "ipaddress", "itertools", "json", "logging", "mailbox", "math",
    "multiprocessing", "optparse", "os", "pathlib", "pdb", "pickle",
    "pickletools", "platform", "plistlib", "pprint", "profile", "pydoc",
    "queue", "random", "re", "sched", "secrets", "selectors", "shlex",
    "shutil", "smtplib", "socket", "socketserver", "sqlite3", "ssl",
    "statistics", "string", "subprocess", "tarfile", "tempfile", "textwrap",
    "threading", "timeit", "traceback", "turtle", "typing", "unittest",
    "urllib.parse", "urllib.request", "uuid", "warnings", "wave", "weakref",
    "xml.dom", "xml.etree.ElementTree", "zipfile", "zoneinfo",
    "numpy", "numpy.linalg", "numpy.fft", "numpy.random",
    # ML-library docstrings (all baked into this image, BSD/Apache): they
    # roughly double the prose pool, which the 16k-vocab BPE row needs —
    # 1.6 MB of text cannot support 16k merges (most pairs fall under
    # min_pair_freq and the trainer early-stops far short)
    "jax", "jax.numpy", "jax.scipy.linalg", "flax.linen", "optax",
    "einops", "chex", "torch", "torch.nn", "torch.optim", "torch.utils.data",
    "transformers",
)


def build_prose_corpus(max_bytes: int = 4_000_000) -> str:
    """Assemble a REAL English prose corpus from what's guaranteed on disk:
    the repo's own markdown docs plus the docstrings of Python's stdlib and
    numpy (PSF/BSD licensed). This is the no-network fallback for a
    loss-goes-down-on-real-text demonstration (VERDICT r2 item 5:
    training on synthetic random tokens supports throughput claims but
    no quality claim): the statistics are genuine
    natural language — skewed toward technical register, which the
    provenance label says out loud.

    Deterministic: fixed module list, sorted member traversal, first-seen
    dedup (inherited/re-exported docstrings appear once)."""
    import importlib
    import inspect

    parts: list[str] = []
    seen: set[bytes] = set()

    def add(text: str | None):
        if text and len(text) > 40:
            # stable digest, NOT builtin hash(): str hashing is salted per
            # process, so a hash() collision could drop different texts in
            # different runs and break the determinism promised above
            h = hashlib.sha1(text.encode("utf-8", "replace")).digest()
            if h not in seen:
                seen.add(h)
                parts.append(text)

    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    for name in sorted(os.listdir(root)):
        if name.endswith(".md"):
            try:
                with open(os.path.join(root, name), encoding="utf-8") as f:
                    add(f.read())
            except OSError:
                continue

    # import EVERYTHING first, then traverse: the ML libraries lazily
    # import each other's internals (flax/transformers pull jax submodules
    # in), which ADDS attributes to modules earlier in this list — a
    # traversal interleaved with imports would see different membership on
    # a second call and break the determinism promised below
    mods = {}
    for modname in _PROSE_MODULES:
        try:
            mods[modname] = importlib.import_module(modname)
        except Exception:  # noqa: BLE001 — any unimportable module is skipped
            continue

    total = lambda: sum(len(p) for p in parts)  # noqa: E731
    for modname in _PROSE_MODULES:
        if total() >= max_bytes:
            break
        mod = mods.get(modname)
        if mod is None:
            continue
        add(inspect.getdoc(mod))
        for _, obj in sorted(vars(mod).items()):
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            # `or ""`: C-extension objects may carry __module__ = None
            if not (getattr(obj, "__module__", "") or "").startswith(
                modname.split(".")[0]
            ):
                continue  # re-exports would duplicate across modules
            add(inspect.getdoc(obj))
            if inspect.isclass(obj):
                for _, member in sorted(vars(obj).items()):
                    doc = getattr(member, "__doc__", None)
                    if isinstance(doc, str):
                        add(doc)
    return "\n\n".join(parts)[:max_bytes]


def load_text_corpus(
    path: str | None = None, max_bytes: int = 4_000_000
) -> tuple[np.ndarray, str]:
    """(byte-level token array uint8, provenance string) for LM training on
    REAL text. Priority: explicit ``path`` (missing file raises — a typo
    must not silently train on the wrong corpus) → ``<repo>/data/corpus.txt``
    (the documented drop-in hook for a user corpus, e.g. TinyStories;
    repo-root-anchored so the hook works from any cwd) →
    :func:`build_prose_corpus`. Byte-level (vocab 256) so no tokenizer
    asset is needed."""
    if path is not None:
        if not os.path.exists(path):
            raise FileNotFoundError(f"corpus file {path!r} does not exist")
        with open(path, "rb") as f:
            raw = f.read(max_bytes)
        return np.frombuffer(raw, np.uint8).copy(), f"user corpus {path}"
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    hook = os.path.join(root, "data", "corpus.txt")
    if os.path.exists(hook):
        with open(hook, "rb") as f:
            raw = f.read(max_bytes)
        return np.frombuffer(raw, np.uint8).copy(), "data/corpus.txt (user-provided)"
    text = build_prose_corpus(max_bytes)
    return (
        np.frombuffer(text.encode("utf-8"), np.uint8).copy(),
        "repo markdown docs + Python stdlib/numpy/ML-library docstrings "
        "(real English prose, technical register; byte-level tokens)",
    )
