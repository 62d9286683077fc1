"""Sharded, async, elastic checkpointing.

Format: a step directory ``step_{n:08d}/`` containing one compressed blob
per tree leaf (raw array bytes) plus ``manifest.json`` (paths, shapes,
dtypes, codec, step metadata). Writes go to ``.tmp-*`` and are renamed
atomically; a ``COMMITTED`` marker makes partially-written checkpoints
invisible to ``latest_step``.

* async: ``save`` snapshots to host memory (device_get) synchronously —
  cheap — then compresses/writes on a background thread so training
  continues; ``wait`` joins before the next save or exit.
* elastic: arrays are saved whole (gathered); ``restore`` places each leaf
  with the *target* sharding, so the same checkpoint restores onto any
  mesh shape (tested: 1 -> 8 devices and back). At true multi-pod scale
  the same manifest format extends to per-shard blobs.
* codecs: zstd when the optional ``zstandard`` package is installed, else
  stdlib zlib. The codec is chosen per checkpoint at save time and
  recorded in the manifest, so restore always picks the right
  decompressor regardless of what the restoring host has installed
  (manifests predating the field are zstd — the only codec that existed).
* verified lineage: every leaf records a crc32 of its raw (uncompressed)
  bytes in the manifest; ``restore`` verifies by default and raises
  :class:`CheckpointCorrupt` naming the offending leaf. ``verify`` audits
  a generation without materializing it, ``generations`` enumerates
  committed steps newest-first, and ``restore_latest_verified`` walks the
  retained generations (``keep``) until one passes — the recovery path
  for a corrupt-or-uncommitted latest checkpoint. ``corrupt`` is the
  matching deterministic fault-injection hook (repro.resilience): one
  seeded byte flip in one leaf blob, manifest and COMMITTED untouched.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import warnings
import zlib

import jax
import numpy as np

from repro.runtime.spans import span

SEP = "/"


class CheckpointCorrupt(RuntimeError):
    """A checkpoint leaf failed checksum/size/decode verification."""


def _compress(codec: str, data: bytes) -> bytes:
    if codec == "zstd":
        import zstandard
        return zstandard.ZstdCompressor(level=1).compress(data)
    if codec == "zlib":
        return zlib.compress(data, 1)
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def _decompress(codec: str, data: bytes) -> bytes:
    if codec == "zstd":
        try:
            import zstandard
        except ImportError as e:
            raise RuntimeError(
                "checkpoint was written with the zstd codec; install the "
                "optional 'zstandard' package to restore it") from e
        return zstandard.ZstdDecompressor().decompress(data)
    if codec == "zlib":
        return zlib.decompress(data)
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def default_codec() -> str:
    """zstd when available (fast, high ratio), zlib otherwise (stdlib)."""
    try:
        import zstandard  # noqa: F401
        return "zstd"
    except ImportError:
        return "zlib"


def _flatten(tree, prefix=()):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_flatten(tree[k], prefix + (str(k),)))
        return out
    return {SEP.join(prefix): tree}


def _unflatten(flat):
    root: dict = {}
    for path, v in flat.items():
        parts = path.split(SEP)
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3,
                 codec: str | None = None):
        self.dir = directory
        self.keep = keep
        self.codec = codec or default_codec()
        if self.codec not in ("zstd", "zlib"):
            # fail fast: the async save path compresses on a daemon
            # thread, where a bad codec would only die in a traceback
            raise ValueError(f"unknown checkpoint codec {self.codec!r}")
        self._thread: threading.Thread | None = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------ save

    def save(self, step: int, tree, *, blocking: bool = False,
             extra: dict | None = None):
        """``extra`` is a JSON-safe dict stored verbatim in the manifest —
        the elastic trainer keeps its AutoTuner/layout state there so a
        restart resumes the ladder (read back via ``load_extra``)."""
        with span("repro.ckpt.save"):
            self.wait()
            flat = _flatten(tree)
            host = {k: np.asarray(jax.device_get(v))
                    for k, v in flat.items()}

        codec = self.codec

        def write():
            with span("repro.ckpt.write"):
                tmp = os.path.join(self.dir, f".tmp-{step:08d}")
                final = os.path.join(self.dir, f"step_{step:08d}")
                shutil.rmtree(tmp, ignore_errors=True)
                os.makedirs(tmp)
                manifest = {"step": step, "codec": codec, "leaves": {}}
                if extra is not None:
                    manifest["extra"] = extra
                for i, (k, v) in enumerate(host.items()):
                    fn = f"leaf_{i:05d}.npy.{codec}"
                    raw = v.tobytes()  # ml_dtypes handles bf16
                    with open(os.path.join(tmp, fn), "wb") as f:
                        f.write(_compress(codec, raw))
                    manifest["leaves"][k] = {
                        "file": fn, "shape": list(v.shape),
                        "dtype": str(v.dtype),
                        # lineage checksum of the raw (uncompressed)
                        # bytes — restore verifies against this by default
                        "crc32": zlib.crc32(raw)}
                with open(os.path.join(tmp, "manifest.json"), "w") as f:
                    json.dump(manifest, f)
                with open(os.path.join(tmp, "COMMITTED"), "w") as f:
                    f.write("ok")
                shutil.rmtree(final, ignore_errors=True)
                os.rename(tmp, final)
                self._gc()

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)

    # ------------------------------------------------------------ load

    def all_steps(self):
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(self.dir, d, "COMMITTED")):
                out.append(int(d[5:]))
        return sorted(out)

    def latest_step(self):
        steps = self.all_steps()
        return steps[-1] if steps else None

    def generations(self):
        """Committed steps newest-first — rollback enumerates these."""
        return list(reversed(self.all_steps()))

    def load_extra(self, step: int) -> dict | None:
        """The manifest's ``extra`` metadata dict (None if absent)."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            return json.load(f).get("extra")

    def _read_leaf(self, d: str, codec: str, k: str, meta: dict,
                   verify: bool) -> np.ndarray:
        path = os.path.join(d, meta["file"])
        with open(path, "rb") as f:
            blob = f.read()
        try:
            raw = _decompress(codec, blob)
        except Exception as e:
            # any codec failure on committed bytes means corruption;
            # surface it as the typed lineage error (note the re-raise)
            raise CheckpointCorrupt(
                f"leaf {k!r} ({meta['file']}) of step {d} failed to "
                f"decompress: {e}") from e
        dtype = np.dtype(meta["dtype"])
        want = int(np.prod(meta["shape"], dtype=np.int64)) * dtype.itemsize
        if len(raw) != want:
            raise CheckpointCorrupt(
                f"leaf {k!r} ({meta['file']}) of step {d}: size mismatch "
                f"({len(raw)} bytes, manifest says {want})")
        if verify and "crc32" in meta and zlib.crc32(raw) != meta["crc32"]:
            raise CheckpointCorrupt(
                f"leaf {k!r} ({meta['file']}) of step {d}: crc32 mismatch "
                f"— checkpoint bytes are corrupt")
        return np.frombuffer(raw, dtype).reshape(meta["shape"])

    def verify(self, step: int) -> list[str]:
        """Audit one generation without materializing it into a tree.
        Returns a list of human-readable issues (empty = verified)."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        if not os.path.exists(os.path.join(d, "COMMITTED")):
            return [f"step {step}: missing COMMITTED marker"]
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            return [f"step {step}: unreadable manifest ({e})"]
        codec = manifest.get("codec", "zstd")
        issues = []
        for k, meta in manifest["leaves"].items():
            try:
                self._read_leaf(d, codec, k, meta, verify=True)
            except (CheckpointCorrupt, OSError) as e:
                issues.append(str(e))
        return issues

    def restore(self, step: int, *, shardings=None, abstract=None,
                verify: bool = True):
        """shardings: optional pytree of jax.sharding.Sharding (elastic
        placement); abstract: optional pytree of ShapeDtypeStruct to
        validate/convert against. Leaves are checksum-verified against
        the manifest by default (``verify=False`` skips the crc pass but
        size/decode corruption still raises :class:`CheckpointCorrupt`)."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        codec = manifest.get("codec", "zstd")  # pre-codec manifests: zstd
        flat = {}
        for k, meta in manifest["leaves"].items():
            flat[k] = self._read_leaf(d, codec, k, meta, verify)
        tree = _unflatten(flat)
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, s: jax.device_put(a, s), tree, shardings)
        elif abstract is not None:
            tree = jax.tree.map(lambda a, sd: jax.numpy.asarray(
                a, dtype=sd.dtype), tree, abstract)
        return tree

    def restore_latest_verified(self, *, shardings=None, abstract=None):
        """Restore the newest generation that passes verification.

        Walks committed generations newest-first; a generation that fails
        checksum/size/decode verification is skipped with a
        RuntimeWarning and the next-older one is tried. Returns
        ``(tree, step)`` or None when no generation survives — the
        recovery ladder's checkpoint rung (corrupt latest falls back to
        an older verified generation; nothing verified means re-init).
        """
        for s in self.generations():
            try:
                tree = self.restore(s, shardings=shardings,
                                    abstract=abstract)
            except (CheckpointCorrupt, OSError, ValueError, KeyError) as e:
                warnings.warn(
                    f"repro.ckpt: checkpoint step {s} failed verification "
                    f"({e}); falling back to the previous generation",
                    RuntimeWarning, stacklevel=2)
                continue
            return tree, s
        return None

    # ----------------------------------------------------- fault hook

    def corrupt(self, step: int, seed: int = 0) -> tuple[str, int]:
        """Deterministic fault-injection hook (repro.resilience): flip
        one seeded byte in one leaf blob of a committed checkpoint. The
        manifest and COMMITTED marker are left intact, so directory
        discovery still trusts the generation — only checksum
        verification can catch the damage. Returns (file, offset)."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        leaves = sorted(manifest["leaves"].values(), key=lambda m: m["file"])
        rng = np.random.default_rng(seed)
        meta = leaves[int(rng.integers(len(leaves)))]
        path = os.path.join(d, meta["file"])
        with open(path, "rb") as f:
            blob = bytearray(f.read())
        off = int(rng.integers(len(blob)))
        blob[off] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(blob))
        return meta["file"], off
