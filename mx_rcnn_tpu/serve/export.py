"""AOT-exported programs + persistent compilation cache (docs/SERVING.md
"Fleet tier", docs/FT.md "Recovery time").

No reference equivalent — the reference binds symbols at process start
and re-traces on every shape change.  This module is the
seconds-scale-cold-start half of the serving fleet (ROADMAP item 2) and
the recovery-time lever of elastic training (ROADMAP item 5):

* an :class:`ExportStore` is a directory of ``jax.export``-serialized
  programs (StableHLO, weights NOT embedded — parameters stay checkpoint
  arguments) plus a ``manifest.json`` naming the config fingerprint,
  bucket/batch shapes, and jax/jaxlib versions the programs were traced
  under, plus the bundled XLA persistent-cache directory the export-time
  verify pass populated;
* a joining replica loads the store, refuses a manifest that does not
  match its own config (a stale export would silently serve different
  semantics), installs the deserialized programs into its
  ``Predictor``'s program cache, and compiles them through the bundled
  persistent cache — skipping BOTH tracing and XLA compilation, the two
  stages that make today's trace-warm startup seconds-to-minutes;
* the export-time verify pass pins every exported program's outputs
  BIT-EQUAL to the live-traced program on the same inputs, so an
  AOT-warmed replica cannot disagree with a trace-warmed one
  (``tests/test_fleet.py`` pins the round trip; ``tools/loadgen.py
  --fleet_bench`` re-checks it cross-process).

Where the persistent compilation cache lives is decided by
``mx_rcnn_tpu/runtime.py — enable_compile_cache``: a store's bundled
``xla_cache/`` is only the directory used when
``JAX_COMPILATION_CACHE_DIR`` does not place the cache elsewhere.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("mx_rcnn_tpu")

MANIFEST_NAME = "manifest.json"
CACHE_SUBDIR = "xla_cache"
VARIABLES_NAME = "variables.npz"


def manifest_sha(root: str) -> str:
    """The store's identity for lineage purposes: sha256 of the
    committed manifest bytes.  A child store records its parent's
    manifest sha as ``parent_sha`` — any change to the parent (programs,
    fingerprints, weights payload) changes the identity, so a forged or
    drifted parent can never satisfy the admission check."""
    path = os.path.join(root, MANIFEST_NAME)
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _flatten_variables(variables, prefix: str = "") -> Dict[str, np.ndarray]:
    """Nested variables dict → flat ``{'a/b/c': array}`` (npz-able)."""
    out: Dict[str, np.ndarray] = {}
    if isinstance(variables, dict):
        for k in sorted(variables):
            out.update(_flatten_variables(variables[k], f"{prefix}{k}/"))
    else:
        out[prefix.rstrip("/")] = np.asarray(variables)
    return out


def _empty_subtrees(variables, prefix: str = "") -> List[str]:
    """Paths of dict subtrees with NO leaves (e.g. a BN-free model's
    ``batch_stats: {}``) — invisible to :func:`_flatten_variables` but
    part of the pytree structure exported programs are called with."""
    out: List[str] = []
    if isinstance(variables, dict):
        if not variables:
            out.append(prefix.rstrip("/"))
        for k in sorted(variables):
            out.extend(_empty_subtrees(variables[k], f"{prefix}{k}/"))
    return out


def _unflatten_variables(flat: Dict[str, np.ndarray]) -> Dict:
    out: Dict = {}
    for key, arr in flat.items():
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out


def variables_fingerprint(variables) -> str:
    """Content fingerprint of a weights pytree (the ``train_fingerprint``
    lineage field): sha256 over sorted leaf paths, dtypes, shapes and
    raw bytes.  Two checkpoints that would serve different boxes can
    never share a fingerprint; re-exporting identical weights always
    reproduces it."""
    h = hashlib.sha256()
    for key, arr in sorted(_flatten_variables(variables).items()):
        a = np.ascontiguousarray(arr)
        h.update(key.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


class ExportMismatch(RuntimeError):
    """The export store's manifest does not match this process's config /
    jax version — loading it would serve programs traced under different
    semantics.  Re-export (``tools/fleet.py export``) instead."""


def _spec_of(tree) -> Any:
    """Pytree of arrays → pytree of ShapeDtypeStructs (the export arg
    template)."""
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape,
                                       np.asarray(a).dtype), tree)


def _describe(tree) -> Any:
    """JSON-able description of an arg pytree's leaf shapes/dtypes (for
    the manifest — human auditing, not validation)."""
    import jax

    leaves = jax.tree.leaves(tree)
    return [[list(np.asarray(a).shape), np.dtype(np.asarray(a).dtype).name]
            for a in leaves]


class ExportStore:
    """A directory of serialized ``jax.export`` programs + manifest.

    Layout::

        <root>/manifest.json       fingerprint, versions, entries
        <root>/<name>.jaxexp       serialized exported program
        <root>/xla_cache/          persistent XLA cache the verify pass
                                   populated (a joining replica's compile
                                   becomes a cache read)

    Writing: ``ExportStore.create(root, cfg)`` → ``add(...)`` per
    program → ``finish()`` (manifest written LAST, atomically — a
    half-written store never verifies).  Reading: ``ExportStore(root)``
    → ``check(cfg)`` → ``load(name)``.
    """

    def __init__(self, root: str):
        self.root = root
        self._manifest: Optional[Dict] = None
        self._entries: Dict[str, Dict] = {}

    # ------------------------------------------------------------------
    # writing
    # ------------------------------------------------------------------

    @classmethod
    def create(cls, root: str, cfg, extra_meta: Dict = None
               ) -> "ExportStore":
        import jax

        from mx_rcnn_tpu.utils.checkpoint import config_fingerprint

        os.makedirs(root, exist_ok=True)
        store = cls(root)
        store._manifest = {
            "kind": "mx_rcnn_tpu_export_store",
            "config_fingerprint": config_fingerprint(cfg),
            "jax_version": jax.__version__,
            "jaxlib_version": getattr(jax, "jaxlib_version", None)
            or __import__("jaxlib").version.__version__,
            "bucket_shapes": [list(b) for b in cfg.bucket.shapes],
            "num_classes": cfg.num_classes,
            "entries": {},
            **(extra_meta or {}),
        }
        return store

    def add(self, name: str, fn: Callable, args: Tuple,
            static_kwargs: Dict = None) -> None:
        """Trace + export ``fn`` (a jitted callable) at the arg shapes of
        ``args`` (arrays or ShapeDtypeStructs) and serialize it into the
        store.  ``static_kwargs`` are baked into the program (they must
        be the static args the live call site passes)."""
        from jax import export as jexport

        from mx_rcnn_tpu.utils.checkpoint import _atomic_write

        exp = jexport.export(fn)(*_spec_of(args), **(static_kwargs or {}))
        blob = exp.serialize()
        path = os.path.join(self.root, f"{name}.jaxexp")
        # the shared durable-write primitive (tmp -> fsync -> rename ->
        # dir-fsync): a crash mid-export can never leave a torn .jaxexp
        # under the committed name (tests/test_fleet.py pins the order)
        _atomic_write(path, blob)
        self._manifest["entries"][name] = {
            "file": f"{name}.jaxexp",
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "args": _describe(args),
            "static": {k: v for k, v in (static_kwargs or {}).items()},
        }

    def add_variables(self, variables) -> None:
        """Bundle the weights payload into the store (npz of flattened
        leaves, sha-pinned like every program entry) and record its
        content fingerprint as the manifest's ``train_fingerprint``.

        Exported programs keep weights as call arguments ("parameters
        stay checkpoint arguments"), so a VERSIONED store must carry the
        weights a rollout is actually shipping — otherwise pulling v2
        would swap programs but keep serving v1's model.  Lives outside
        ``entries`` (those are jax programs; ``load``/``names`` must not
        trip over a payload blob)."""
        import io

        from mx_rcnn_tpu.utils.checkpoint import _atomic_write

        buf = io.BytesIO()
        np.savez(buf, **_flatten_variables(variables))
        blob = buf.getvalue()
        _atomic_write(os.path.join(self.root, VARIABLES_NAME), blob)
        self._manifest["variables"] = {
            "file": VARIABLES_NAME,
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
            # leaf-less subtrees (a BN-free model's empty batch_stats)
            # vanish in the npz flatten; the exported programs' calling
            # convention still requires them, so record their paths and
            # rebuild them on load
            "empty_subtrees": _empty_subtrees(variables),
        }
        self._manifest["train_fingerprint"] = \
            variables_fingerprint(variables)

    def load_variables(self) -> Dict:
        """Load the bundled weights payload (sha-verified, typed refusal
        on corruption — same contract as :meth:`load`)."""
        import io

        m = self.manifest()
        entry = m.get("variables")
        if entry is None:
            raise ExportMismatch(
                f"export store {self.root} bundles no variables payload "
                "— it cannot ship a model version by itself")
        path = os.path.join(self.root, entry["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            raise ExportMismatch(
                f"export store {self.root} is missing {entry['file']} "
                "although the manifest names it — the store is corrupt; "
                "re-export") from None
        sha = hashlib.sha256(blob).hexdigest()
        if sha != entry["sha256"]:
            raise ExportMismatch(
                f"variables payload {path} is corrupt: sha256 {sha} != "
                f"manifest {entry['sha256']}")
        with np.load(io.BytesIO(blob)) as z:
            variables = _unflatten_variables({k: z[k] for k in z.files})
        for path in entry.get("empty_subtrees", []):
            node = variables
            parts = [p for p in path.split("/") if p]
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            if parts:
                node.setdefault(parts[-1], {})
        return variables

    # ------------------------------------------------------------------
    # lineage (docs/SERVING.md "Rollout tier")
    # ------------------------------------------------------------------

    @property
    def version(self) -> Optional[str]:
        """The store's version id, or None for a legacy version-less
        store (every store exported before the rollout plane)."""
        return self.manifest().get("version")

    @property
    def parent_sha(self) -> Optional[str]:
        return self.manifest().get("parent_sha")

    def check_lineage(self, known_parents=None,
                      expect_train_fingerprint: str = None) -> Dict:
        """Rollout admission over the lineage fields — run IN ADDITION
        to :meth:`check` (which pins config/jax/bucket/quant semantics):

        * ``known_parents`` (iterable of manifest shas): the versions
          this fleet currently serves.  A versioned store whose
          ``parent_sha`` is not among them is REFUSED (unknown parent —
          a v2 built against some other fleet's v1 must not land here);
          a versioned store recording no parent at all is likewise
          refused when a parent set is required.
        * ``expect_train_fingerprint``: refusal when the manifest's
          recorded ``train_fingerprint`` differs — the
          fingerprint-mismatch rule (a store whose recorded weights
          identity disagrees with what the operator pinned).

        Back-compat: a manifest WITHOUT a ``version`` field is a legacy
        store — it predates lineage, carries no claims, and admits
        unchanged (same idiom as quant admission's "old manifests
        without the key count as fp stores"); pinned by
        tests/test_rollout.py."""
        m = self.manifest()
        if "version" not in m:
            return {"version": None, "parent_sha": None, "legacy": True}
        version = m["version"]
        parent = m.get("parent_sha")
        if known_parents is not None:
            known = set(known_parents)
            if parent is None:
                raise ExportMismatch(
                    f"export store {self.root} (version {version!r}) "
                    "records no parent_sha but this fleet requires "
                    "lineage — refusing an unrooted version")
            if parent not in known:
                raise ExportMismatch(
                    f"export store {self.root} (version {version!r}) "
                    f"has unknown parent {parent[:12]}… — not among the "
                    f"{len(known)} version(s) this fleet serves")
        recorded_fp = m.get("train_fingerprint")
        if (expect_train_fingerprint is not None
                and recorded_fp != expect_train_fingerprint):
            raise ExportMismatch(
                f"export store {self.root} (version {version!r}) "
                f"train_fingerprint {str(recorded_fp)[:12]}… != expected "
                f"{expect_train_fingerprint[:12]}… — the shipped weights "
                "are not the weights this rollout was approved for")
        return {"version": version, "parent_sha": parent,
                "train_fingerprint": recorded_fp, "legacy": False}

    def finish(self) -> str:
        """Commit the manifest (written LAST: its presence means every
        program file it names is fully on disk).  Shares
        ``utils/checkpoint._atomic_write`` with every other commit point
        in the tree — the hand-rolled tmp→fsync→replace this method used
        to carry skipped the directory fsync, so a host crash could lose
        the 'committed' manifest (persistlint PL103; the crashsim
        ``export_nodirfsync`` arm reproduces the lost commit)."""
        from mx_rcnn_tpu.utils.checkpoint import _atomic_write

        path = os.path.join(self.root, MANIFEST_NAME)
        _atomic_write(path, json.dumps(self._manifest, indent=1,
                                       sort_keys=True).encode())
        return path

    # ------------------------------------------------------------------
    # reading
    # ------------------------------------------------------------------

    def manifest(self) -> Dict:
        if self._manifest is None:
            path = os.path.join(self.root, MANIFEST_NAME)
            with open(path) as f:
                self._manifest = json.load(f)
        return self._manifest

    def cache_dir(self) -> str:
        return os.path.join(self.root, CACHE_SUBDIR)

    def check(self, cfg, allow_mismatch: bool = False,
              quant_fingerprint: str = None) -> Dict:
        """Admission check before any program loads: config fingerprint,
        bucket shapes and jax version must match this process, else the
        store serves different semantics than a live trace would —
        refuse (``ExportMismatch``) unless ``allow_mismatch`` downgrades
        to a WARNING (debugging only).

        ``quant_fingerprint``: the loading process's OWN calibration
        fingerprint (``Predictor.quant_fingerprint``; None when
        ``cfg.quant`` is off).  The manifest's recorded quant knobs —
        dtype/mode/estimator/weight_bits AND the calibration
        fingerprint — must agree exactly: a quantized store can never
        warm an fp replica, an fp store can never warm a quantized one,
        and two differently-calibrated quant processes can never share
        programs (docs/SERVING.md "Quantized exports")."""
        import jax

        from mx_rcnn_tpu.utils.checkpoint import config_fingerprint

        m = self.manifest()
        problems: List[str] = []
        fp = config_fingerprint(cfg)
        if m.get("config_fingerprint") != fp:
            problems.append(
                f"config fingerprint {m.get('config_fingerprint')} != "
                f"this run's {fp}")
        if m.get("jax_version") != jax.__version__:
            problems.append(f"jax {m.get('jax_version')} != running "
                            f"{jax.__version__}")
        want = [list(b) for b in cfg.bucket.shapes]
        if m.get("bucket_shapes") != want:
            problems.append(f"bucket shapes {m.get('bucket_shapes')} != "
                            f"{want}")
        # serving-semantics knobs live OUTSIDE the train-config
        # fingerprint (serve/test sections are deliberately excluded
        # from it), but they are baked into the exported programs as
        # static args — a drifted value would silently serve different
        # boxes.  Compare every recorded knob against this process.
        for key, live in (("serve_batch_size", cfg.serve.batch_size),
                          ("nms_thresh", cfg.test.nms),
                          ("serve_score_thresh", cfg.serve.score_thresh),
                          ("num_classes", cfg.num_classes)):
            if key in m and m[key] != live:
                problems.append(f"{key} {m[key]} != this run's {live}")
        # quantization admission (docs/PERF.md "Quantized inference"):
        # the recorded quant block must equal this process's — None vs
        # None for fp, or every knob INCLUDING the calibration
        # fingerprint for quant.  Old manifests without the key count
        # as fp stores.
        recorded = m.get("quant")
        if getattr(cfg, "quant", None) is not None and cfg.quant.enabled:
            from mx_rcnn_tpu.ops.quant import quant_manifest_meta

            live_q = quant_manifest_meta(cfg.quant, quant_fingerprint)
        else:
            live_q = None
        if recorded != live_q:
            problems.append(
                f"quant knobs {recorded} != this run's {live_q} — "
                "quantized and fp programs must never mix unknowingly")
        if problems:
            msg = (f"export store {self.root} does not match this "
                   f"process: " + "; ".join(problems))
            if not allow_mismatch:
                raise ExportMismatch(msg)
            logger.warning("%s (allow_mismatch set — loading anyway)", msg)
        return m

    def load(self, name: str) -> Callable:
        """Deserialize one program and wrap it in ``jax.jit`` so repeat
        calls dispatch through the compiled-executable cache.  The first
        call compiles the StableHLO — a persistent-cache READ when the
        bundled ``xla_cache/`` is armed (``runtime.enable_compile_cache``)."""
        import jax
        from jax import export as jexport

        entry = self.manifest()["entries"][name]
        path = os.path.join(self.root, entry["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            # a manifest naming a missing program means the store lost
            # files after its commit point — refuse through the
            # documented surface, not a raw ENOENT (crashsim found this:
            # the recovery path's refusals must be typed)
            raise ExportMismatch(
                f"export store {self.root} is missing {entry['file']} "
                f"although the manifest names it — the store is "
                "corrupt; re-export") from None
        sha = hashlib.sha256(blob).hexdigest()
        if sha != entry["sha256"]:
            raise ExportMismatch(
                f"export {path} is corrupt: sha256 {sha} != manifest "
                f"{entry['sha256']}")
        return jax.jit(jexport.deserialize(blob).call)

    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.manifest()["entries"]))


# ---------------------------------------------------------------------------
# serving-program export (the fleet tier's AOT artifacts)
# ---------------------------------------------------------------------------

def serve_fwd_name(bucket: Tuple[int, int], batch: int) -> str:
    return f"serve_fwd_{bucket[0]}x{bucket[1]}_b{batch}"


def eval_fwd_name(bucket: Tuple[int, int], batch: int) -> str:
    return f"eval_fwd_{bucket[0]}x{bucket[1]}_b{batch}"


SERVE_POST = "serve_post"


def _dummy_batch(bucket: Tuple[int, int], n: int, seed: int = 0
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic non-trivial verify inputs (zeros would let a broken
    program pass bit-equality on degenerate outputs)."""
    bh, bw = bucket
    rng = np.random.RandomState(seed + bh * 7 + bw)
    images = rng.rand(n, bh, bw, 3).astype(np.float32) * 255.0
    im_info = np.tile(np.array([bh, bw, 1.0], np.float32), (n, 1))
    return images, im_info


def export_serve_programs(predictor, cfg, root: str, *,
                          eval_batch: int = None, verify: bool = True,
                          version: str = None, parent: str = None,
                          bundle_variables: bool = False) -> Dict:
    """Export every per-bucket serving program + the shared postprocess
    (+ the eval ``Predictor`` step at ``eval_batch`` rows) into an
    :class:`ExportStore` at ``root``, and — unless ``verify=False`` —
    pin each exported program's outputs BIT-EQUAL to the live-traced
    program on deterministic inputs.  The verify pass doubles as the
    persistent-cache population step: run it with
    ``runtime.enable_compile_cache(store.cache_dir())`` armed and a joining
    replica's compiles become cache reads.

    Lineage (docs/SERVING.md "Rollout tier"): ``version`` stamps the
    store with an explicit version id, ``parent`` (a parent store ROOT
    or a manifest sha) records what this version supersedes, and
    ``bundle_variables`` ships the weights payload inside the store so
    a rollout pull delivers the whole model.  All three default off —
    version-less exports stay byte-compatible with every pre-rollout
    consumer.

    Returns a report dict (programs, bytes, verified flags) that
    ``tools/fleet.py export`` prints and the manifest summarizes.
    """
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.core.tester import _postprocess_batch, tiled_bbox_stats

    model = predictor.model
    variables = predictor.variables
    n = cfg.serve.batch_size
    buckets = [tuple(b) for b in cfg.bucket.shapes]
    # quant block (admission contract — see ExportStore.check): a
    # quantized predictor's programs carry its recipe + calibration
    # fingerprint in the manifest; fp stores record None explicitly
    quant_meta = None
    if cfg.quant.enabled:
        from mx_rcnn_tpu.ops.quant import quant_manifest_meta

        quant_meta = quant_manifest_meta(cfg.quant,
                                         predictor.quant_fingerprint)
    extra_meta = {
        "serve_batch_size": n,
        "eval_batch_size": eval_batch,
        "nms_thresh": cfg.test.nms,
        "serve_score_thresh": cfg.serve.score_thresh,
        "quant": quant_meta,
    }
    if version is not None:
        extra_meta["version"] = version
        if parent is not None and os.path.isdir(str(parent)):
            parent = manifest_sha(str(parent))
        extra_meta["parent_sha"] = parent
    store = ExportStore.create(root, cfg, extra_meta=extra_meta)
    if bundle_variables:
        store.add_variables(variables)
    report: Dict = {"root": root, "programs": [], "verified": verify,
                    "bit_equal": None}

    def fwd_fn():
        @jax.jit
        def fn(variables, images, im_info):
            return model.apply(variables, images, im_info)

        return fn

    stds, means = tiled_bbox_stats(cfg, cfg.num_classes)
    all_equal = True
    post_done = False
    # per-bucket forward at the serve batch (and the eval batch when it
    # differs) + ONE postprocess at the serve shapes
    sizes = [n] + ([eval_batch] if eval_batch and eval_batch != n else [])
    for bucket in buckets:
        for rows in sizes:
            images, im_info = _dummy_batch(bucket, rows)
            fn = fwd_fn()
            name = (serve_fwd_name(bucket, rows) if rows == n
                    else eval_fwd_name(bucket, rows))
            store.add(name, fn, (variables, images, im_info))
            if verify:
                live = fn(variables, images, im_info)
                loaded = _load_unfinished(store, name)
                got = loaded(variables, images, im_info)
                eq = _bit_equal(live, got)
                all_equal &= eq
                report["programs"].append(
                    {"name": name, "bit_equal": eq})
                if rows == n and not post_done:
                    # the postprocess program, exported at the shapes the
                    # forward actually produces (and verified on REAL
                    # forward outputs, not synthetic tensors)
                    rois, roi_valid, cls_prob, deltas = live
                    post_args = (rois, roi_valid, cls_prob, deltas,
                                 jnp.asarray(im_info),
                                 jnp.asarray(im_info[:, 2]), stds, means)
                    statics = {"nms_thresh": cfg.test.nms,
                               "score_thresh": cfg.serve.score_thresh}
                    store.add(SERVE_POST, _postprocess_batch, post_args,
                              static_kwargs=statics)
                    live_post = _postprocess_batch(*post_args, **statics)
                    got_post = _load_unfinished(store, SERVE_POST)(
                        *post_args)
                    eq = _bit_equal(live_post, got_post)
                    all_equal &= eq
                    report["programs"].append(
                        {"name": SERVE_POST, "bit_equal": eq})
                    post_done = True
            else:
                report["programs"].append({"name": name})
    if not verify and not post_done:
        # still need the postprocess export: trace shapes via one live run
        images, im_info = _dummy_batch(buckets[0], n)
        rois, roi_valid, cls_prob, deltas = fwd_fn()(variables, images,
                                                     im_info)
        post_args = (rois, roi_valid, cls_prob, deltas,
                     jnp.asarray(im_info), jnp.asarray(im_info[:, 2]),
                     stds, means)
        store.add(SERVE_POST, _postprocess_batch, post_args,
                  static_kwargs={"nms_thresh": cfg.test.nms,
                                 "score_thresh": cfg.serve.score_thresh})
        report["programs"].append({"name": SERVE_POST})
    manifest_path = store.finish()
    report["manifest"] = manifest_path
    report["bit_equal"] = all_equal if verify else None
    report["bytes"] = sum(e["bytes"]
                          for e in store.manifest()["entries"].values())
    if verify and not all_equal:
        raise ExportMismatch(
            "exported program outputs are NOT bit-equal to the live "
            "trace — refusing to commit a store that would serve "
            "different results (see report)")
    return report


def _load_unfinished(store: ExportStore, name: str) -> Callable:
    """Load from a store still being written (manifest not committed):
    deserialize the just-written blob directly."""
    import jax
    from jax import export as jexport

    path = os.path.join(store.root, f"{name}.jaxexp")
    with open(path, "rb") as f:
        return jax.jit(jexport.deserialize(f.read()).call)


def _bit_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).shape == np.asarray(y).shape
        and (np.asarray(x) == np.asarray(y)).all()
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# train-step export (ROADMAP item 5 — AOT step artifact)
# ---------------------------------------------------------------------------

def export_train_step(cfg, *, out_dir: str, num_devices: int = 1,
                      grad_accum: int = 1, seed: int = 0,
                      verify: bool = True) -> Dict:
    """Export the jitted train step for the current recipe/topology as a
    portable AOT artifact (``<out_dir>/train_step.jaxexp`` + manifest),
    verified bit-equal against the live-traced step on one synthetic
    batch.

    The exported step takes ``(state, batch, key)`` like the live one
    but carries NO donation metadata (``jax.export`` serializes the
    program, not the buffer-aliasing policy) — it is the
    scheduler-shippable program artifact and the persistent-cache
    pre-warmer, not a drop-in replacement for the fit loop's donating
    step.  The compile-skip on restart comes from the persistent cache
    ``tools/train.py`` arms at start-up (``runtime.enable_compile_cache``);
    docs/FT.md "Recovery time" has the measured deltas.
    """
    import jax

    from mx_rcnn_tpu.core.train import make_train_step, setup_training
    from mx_rcnn_tpu.models import build_model

    if num_devices != 1:
        raise NotImplementedError(
            "train-step export currently covers the single-device step "
            "(the elastic relaunch path compiles the sharded step "
            "through the persistent cache instead)")
    model = build_model(cfg)
    bh, bw = cfg.bucket.shapes[0]
    key = jax.random.PRNGKey(seed)
    state, tx = setup_training(model, cfg, key,
                               (cfg.train.batch_images, bh, bw, 3),
                               steps_per_epoch=100)
    step = make_train_step(model, cfg, tx, grad_accum=grad_accum)
    batch = _synthetic_train_batch(cfg, seed)
    # export over FLATTENED leaves: the TrainState/optax-state pytree
    # types (EmptyState, ScaleByAdamState, flax structs) have no
    # jax.export serialization registered, and registering every
    # optimizer internal would couple the artifact to optax's private
    # layout — a flat (arrays in) -> (arrays out) program sidesteps the
    # whole class.  ``load_train_step`` rebuilds the treedefs from the
    # caller's own live state (same recipe => same structure).
    args_leaves, args_tree = jax.tree.flatten((state, batch, key))

    @jax.jit
    def step_flat(*leaves):
        s, b, k = jax.tree.unflatten(args_tree, leaves)
        return tuple(jax.tree.leaves(step(s, b, k)))

    store = ExportStore.create(out_dir, cfg, extra_meta={
        "train_step": True, "num_devices": num_devices,
        "grad_accum": grad_accum,
        "batch_images": cfg.train.batch_images})
    store.add("train_step", step_flat, tuple(args_leaves))
    report: Dict = {"root": out_dir, "programs": [{"name": "train_step"}],
                    "verified": verify, "bit_equal": None}
    if verify:
        live = jax.jit(step)(state, batch, key)
        got_flat = _load_unfinished(store, "train_step")(*args_leaves)
        got = jax.tree.unflatten(jax.tree.structure(live), got_flat)
        eq = _bit_equal(live, got)
        report["bit_equal"] = eq
        report["programs"][0]["bit_equal"] = eq
        if not eq:
            raise ExportMismatch(
                "exported train step is NOT bit-equal to the live trace")
    report["manifest"] = store.finish()
    report["bytes"] = store.manifest()["entries"]["train_step"]["bytes"]
    return report


def load_train_step(store: ExportStore, state, batch, key) -> Callable:
    """Wrap the exported flat train-step program back into the live
    ``(state, batch, key) -> (state, metrics)`` signature.  The flat
    program carries no pytree structure, so the caller supplies live
    templates (a state/batch built from the SAME recipe — ``check``
    already pinned the config fingerprint); the output treedef is
    reconstructed by shape: the leading output leaves refill the state
    structure, the rest the metrics dict (keys recorded at export are in
    the manifest for auditing)."""
    import jax

    fn = store.load("train_step")
    args_tree = jax.tree.structure((state, batch, key))
    state_tree = jax.tree.structure(state)
    n_state = state_tree.num_leaves

    def wrapped(s, b, k):
        leaves = jax.tree.leaves((s, b, k))
        if len(leaves) != args_tree.num_leaves:
            raise ExportMismatch(
                f"train-step args have {len(leaves)} leaves, export "
                f"was traced with {args_tree.num_leaves}")
        out = fn(*leaves)
        new_state = jax.tree.unflatten(state_tree, out[:n_state])
        return new_state, list(out[n_state:])

    return wrapped


def _synthetic_train_batch(cfg, seed: int):
    """One deterministic training batch at the recipe's static shapes
    (synthetic pixels/boxes — the export traces shapes, not content)."""
    from mx_rcnn_tpu.data import load_gt_roidb
    from mx_rcnn_tpu.data.loader import AnchorLoader

    kw = {}
    if cfg.dataset.name.startswith("synthetic"):
        kw["num_images"] = max(cfg.train.batch_images * 2, 4)
    _, roidb = load_gt_roidb(cfg, training=True, **kw)
    loader = AnchorLoader(roidb, cfg, batch_images=cfg.train.batch_images,
                          shuffle=False, seed=seed)
    return next(iter(loader))
