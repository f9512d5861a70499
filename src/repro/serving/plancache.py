"""Persistent, content-addressed store for compiled execution plans.

The paper's amortization argument says the offline cost — Theorem-1
scheduling plus Connection Reordering — is paid once and served from
forever.  Without persistence "once" really means "once per process":
every server restart re-annealed the same network.  ``PlanStore`` closes
that gap:

  * the cache key is a sha256 over the *content* of the network (each
    layer's block pattern, weights, bias, tile shape) plus every engine
    setting that affects the schedule arrays (``reorder``, ``M_tiles``,
    ``reorder_iters``, ``seed``, ``policy``, ``fuse``) and the artifact
    format version — object identity never matters, so any process that
    builds the same pruned network hits the same entry;
  * the stored artifact is the whole-DAG connection ``order`` (everything
    else re-derives from it deterministically), the flat-schedule prefetch
    arrays (used to verify the rebuild bit-for-bit), and the plan's
    ``IOReport`` — written through ``repro.checkpoint``'s atomic manifest
    machinery, so a crash mid-write never corrupts an entry;
  * a hit calls ``Engine.compile_with_order``: zero annealer iterations,
    no I/O re-simulation, outputs bit-identical to the cold compile the
    order came from.  A stored entry whose arrays no longer match the
    rebuild (schedule-packing code drift) is discarded as a miss, so stale
    caches self-heal.

Backend and activation are deliberately NOT part of the key: the connection
order is backend-independent (all backends walk the same arrays) and the
activation only changes the epilogue, not the schedule — one annealed entry
serves every backend.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional, Sequence, Tuple, Union

import numpy as np

from repro.checkpoint.store import (
    manifest_exists,
    read_manifest_dir,
    write_manifest_dir,
)
from repro.core.blocksparse import BlockFFNN, BSRLayer, GLUBlock
from repro.kernels.ops import resolve_weight_dtype
from repro.engine import (
    Engine,
    ExecutionPlan,
    IOReport,
    Mesh,
    ShardedExecutionPlan,
    ShardedIOReport,
)

FORMAT_VERSION = 1


def _layers_of(net: Union[BlockFFNN, Sequence[BSRLayer]]):
    if isinstance(net, GLUBlock) or getattr(net, "glu", False):
        raise ValueError("the plan store does not handle a gated (GLU) "
                         "block")
    return net.layers if isinstance(net, BlockFFNN) else list(net)


def layers_fingerprint(net: Union[BlockFFNN, Sequence[BSRLayer]]) -> str:
    """sha256 over every layer's structure AND weights.

    The schedule only depends on the block *pattern*, but keying on weights
    too means a repruned or retrained network can never silently serve a
    stale schedule-with-matching-shape.
    """
    h = hashlib.sha256()
    for lay in _layers_of(net):
        h.update(json.dumps([lay.n_in, lay.n_out, lay.block_m, lay.block_n,
                             lay.nnz_blocks]).encode())
        h.update(np.ascontiguousarray(lay.rows, dtype=np.int32).tobytes())
        h.update(np.ascontiguousarray(lay.cols, dtype=np.int32).tobytes())
        h.update(np.ascontiguousarray(lay.blocks).tobytes())
        h.update(np.ascontiguousarray(lay.bias).tobytes())
    return h.hexdigest()


def plan_cache_key(engine: Engine,
                   net: Union[BlockFFNN, Sequence[BSRLayer]],
                   mesh: Optional[Mesh] = None) -> str:
    """Content-addressed key: layer hash + schedule-affecting settings.

    The mesh topology is part of the key — a sharded plan's per-shard
    orders are meaningless under any other partition, so changing the mesh
    shape (including sharded vs unsharded) must be a miss.  ``mesh`` /
    ``max_move_span`` / ``gate`` / ``weight_dtype`` enter the dict only
    when set (non-default), so entries written by earlier store versions
    stay warm.  A quantized plan's entry stores narrow blocks + scales, so
    f32 and quantized plans of the same net must never alias.
    """
    settings = {
        "format": FORMAT_VERSION,
        "layers": layers_fingerprint(net),
        "reorder": bool(engine.reorder),
        "M_tiles": int(engine.M_tiles),
        "reorder_iters": int(engine.reorder_iters),
        "seed": int(engine.seed),
        "policy": engine.policy,
        "fuse": bool(engine.fuse),
    }
    if getattr(engine, "max_move_span", None):
        settings["max_move_span"] = int(engine.max_move_span)
    if getattr(engine, "gate", False):
        # gated and ungated plans must never alias (their lowered forwards
        # differ even though the schedule arrays are identical)
        settings["gate"] = True
    wdt = resolve_weight_dtype(getattr(engine, "weight_dtype", "f32"))
    if wdt != "f32":
        settings["weight_dtype"] = wdt
    if mesh is not None:
        settings["mesh"] = [int(mesh.model), int(mesh.data)]
    return hashlib.sha256(
        json.dumps(settings, sort_keys=True).encode()).hexdigest()


class PlanStore:
    """Directory of plan artifacts keyed by :func:`plan_cache_key`.

    ``fault_injector`` (a :class:`repro.serving.resilience.FaultInjector`)
    fires the ``store.load`` chaos site inside the read path.  An entry
    that raises on load or fails its self-heal verify is moved to
    ``<root>/quarantine/`` (counted in ``self.quarantined``) and treated
    as a miss — the bad bytes are preserved for inspection but can never
    be retried in a loop, because the recompile overwrites the live slot.
    """

    def __init__(self, root: str, fault_injector=None, tracer=None):
        from repro.obs.trace import NULL_TRACER
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.injector = fault_injector
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.quarantined = 0
        # per-key in-process compile locks: two threads warm-starting the
        # same network (e.g. concurrent SparseServer.swap calls) serialize
        # on the key, so the loser hits the entry the winner just wrote
        # instead of paying the annealing a second time
        self._locks_mu = threading.Lock()
        self._key_locks: dict = {}

    def _key_lock(self, key: str) -> threading.Lock:
        with self._locks_mu:
            lock = self._key_locks.get(key)
            if lock is None:
                lock = self._key_locks[key] = threading.Lock()
            return lock

    def path_for(self, key: str) -> str:
        return os.path.join(self.root, f"plan_{key}")

    def contains(self, engine: Engine,
                 net: Union[BlockFFNN, Sequence[BSRLayer]],
                 mesh: Optional[Mesh] = None) -> bool:
        return manifest_exists(
            self.path_for(plan_cache_key(engine, net, mesh)))

    def evict(self, engine: Engine,
              net: Union[BlockFFNN, Sequence[BSRLayer]],
              mesh: Optional[Mesh] = None) -> bool:
        """Remove the entry for this (engine, net, mesh), if any.  Returns
        True when something was removed (used e.g. by the benchmark to force
        a genuinely cold start against a reused store directory)."""
        path = self.path_for(plan_cache_key(engine, net, mesh))
        if os.path.isdir(path):
            import shutil
            shutil.rmtree(path, ignore_errors=True)
            return True
        return False

    def keys(self):
        if not os.path.isdir(self.root):
            return []
        return sorted(n[len("plan_"):] for n in os.listdir(self.root)
                      if n.startswith("plan_")
                      and manifest_exists(os.path.join(self.root, n)))

    # ------------------------------------------------------------------ #
    def _quarantine(self, path: str, reason: str) -> None:
        """Move a bad entry out of the live store into ``quarantine/``.

        Deleting it outright would lose the evidence; leaving it in place
        would re-fail every load until someone recompiles.  Quarantine does
        neither: the live slot is freed (the next ``get_or_compile``
        recompiles and writes a fresh entry) and the bad bytes are kept —
        suffixed ``.1``, ``.2``, … if the same key lands here repeatedly.
        """
        import shutil
        qdir = os.path.join(self.root, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        dest = os.path.join(qdir, os.path.basename(path))
        n = 0
        while os.path.exists(dest):
            n += 1
            dest = os.path.join(qdir, f"{os.path.basename(path)}.{n}")
        try:
            os.replace(path, dest)
            with open(os.path.join(dest, "QUARANTINE_REASON.txt"),
                      "w") as fh:
                fh.write(reason + "\n")
        except OSError:
            # cross-device move or a racing writer: freeing the live slot
            # is the part that matters
            shutil.rmtree(path, ignore_errors=True)
        self.quarantined += 1
        if self.tracer.enabled:
            self.tracer.event("store.quarantine",
                              entry=os.path.basename(path), reason=reason)

    def _clean_partial(self, path: str) -> None:
        """Remove wreckage a crashed writer left behind: a ``.tmp`` staging
        dir, or a final dir with no manifest.  Either way the entry never
        became valid — a miss, not an error."""
        import shutil
        tmp = path + ".tmp"
        if os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
        if os.path.isdir(path) and not manifest_exists(path):
            shutil.rmtree(path, ignore_errors=True)

    # ------------------------------------------------------------------ #
    def put(self, engine: Engine,
            plan: Union[ExecutionPlan, ShardedExecutionPlan]) -> str:
        """Persist a compiled plan's schedule artifact (atomic).

        A :class:`ShardedExecutionPlan` stores one connection order (plus
        flat-schedule verification arrays) per shard and the per-layer
        partition assignment, keyed on its mesh topology.
        """
        sharded = isinstance(plan, ShardedExecutionPlan)
        mesh = plan.mesh if sharded else None
        key = plan_cache_key(engine, plan.block_ffnn, mesh)
        extra = {
            "format": FORMAT_VERSION,
            "key": key,
            "n_layers": len(plan.shards[0].layers) if sharded
            else len(plan.layers),
            "io": (plan.io_report() if sharded else plan.io).to_dict(),
            "compile_s": plan.compile_s,
            "annealer_iters": plan.annealer_iters,
        }
        if sharded:
            extra["mesh"] = [int(mesh.model), int(mesh.data)]
            extra["n_shards"] = len(plan.shards)
        else:
            extra["fused"] = plan.fused
        return write_manifest_dir(self.path_for(key), plan.artifact_arrays(),
                                  extra)

    def load(
        self,
        engine: Engine,
        net: Union[BlockFFNN, Sequence[BSRLayer]],
        backend: Optional[str] = None,
        verify: bool = True,
        mesh: Optional[Mesh] = None,
    ) -> Optional[Union[ExecutionPlan, ShardedExecutionPlan]]:
        """Rebuild a plan from a stored artifact, or None on miss.

        ``verify`` additionally checks that the flat-schedule arrays
        rebuilt from the stored order are bit-identical to the stored
        ones; a mismatch (artifact written by incompatible packing code)
        is treated as a miss.  With ``mesh``, the per-shard orders are
        rebuilt through ``Engine.compile_sharded_with_orders`` (zero
        annealer iterations per shard) and every shard — plus the stored
        partition assignment — is verified.
        """
        key = plan_cache_key(engine, net, mesh)
        path = self.path_for(key)
        if not manifest_exists(path):
            # a crashed writer may have left a .tmp staging dir or a
            # manifest-less final dir — clean the wreckage so the slot is a
            # plain (recompilable) miss, never an error
            self._clean_partial(path)
            return None
        try:
            if self.injector is not None:
                self.injector.fire("store.load")
            arrays, extra = read_manifest_dir(path)
            if extra.get("format") != FORMAT_VERSION:
                # not corrupt — written by a different store version; leave
                # it alone (an older process may still be serving from it)
                return None
            if mesh is None:
                io = IOReport.from_dict(extra["io"])
            else:
                if extra.get("mesh") != [int(mesh.model), int(mesh.data)]:
                    return None
                n_shards = int(extra["n_shards"])
                sio = ShardedIOReport.from_dict(extra["io"])
                orders = [arrays[f"s{i}_order"] for i in range(n_shards)]
        except (OSError, KeyError, ValueError, TypeError) as e:
            # corrupt/unreadable entry (crc mismatch, mangled manifest,
            # wrong-typed metadata field): quarantine it — a miss that
            # recompiles into a fresh entry, never a load loop over the
            # same bad bytes — self-healing, not fatal
            self._quarantine(path, f"load raised {type(e).__name__}: {e}")
            return None
        if mesh is None:
            plan = engine.compile_with_order(net, arrays["order"], backend,
                                             io=io)
            if verify and not self._matches(plan, arrays):
                self._quarantine(path, "self-heal verify failed: rebuilt "
                                       "flat schedule != stored arrays")
                return None
            return plan
        if len(sio.per_shard) != n_shards:
            self._quarantine(path, "self-heal verify failed: stored shard "
                                   "count != per-shard reports")
            return None
        plan = engine.compile_sharded_with_orders(
            net, mesh, orders, backend, ios=list(sio.per_shard))
        if verify and not self._matches_sharded(plan, arrays):
            self._quarantine(path, "self-heal verify failed: rebuilt shard "
                                   "arrays != stored arrays")
            return None
        return plan

    @staticmethod
    def _matches(plan: ExecutionPlan, arrays: dict) -> bool:
        stored_fused = any(k.startswith("flat_") for k in arrays)
        if plan.fused != stored_fused:
            return False
        if plan.flat is None:
            return True
        for name in ("rows", "cols", "first", "last", "layer_id",
                     "hbm_row", "out_tile", "bias_idx"):
            if not np.array_equal(np.asarray(getattr(plan.flat, name)),
                                  arrays[f"flat_{name}"]):
                return False
        if plan.flat.scales is not None:
            # quantized entries also verify the stored narrow blocks +
            # scales byte-for-byte against the deterministic requantization
            # (bytes, not values: narrow floats have NaN patterns
            # np.array_equal would mis-judge)
            for name, rebuilt in (("flat_qblocks", plan.flat.blocks),
                                  ("flat_scales", plan.flat.scales)):
                stored = arrays.get(name)
                rebuilt = np.asarray(rebuilt)
                if (stored is None or stored.dtype != rebuilt.dtype
                        or stored.shape != rebuilt.shape
                        or stored.tobytes() != rebuilt.tobytes()):
                    return False
        return True

    @classmethod
    def _matches_sharded(cls, plan: ShardedExecutionPlan,
                         arrays: dict) -> bool:
        """Every shard's rebuilt arrays — and the partition itself — must
        match the stored artifact bit-for-bit; any drift is a miss."""
        stored = plan.artifact_arrays()
        for k in range(plan.n_layers):
            name = f"assign_l{k}"
            if name not in arrays or \
                    not np.array_equal(arrays[name], stored[name]):
                return False
        for s, shard in enumerate(plan.shards):
            sub = {name[len(f"s{s}_"):]: arr for name, arr in arrays.items()
                   if name.startswith(f"s{s}_")}
            if not sub or not cls._matches(shard, sub):
                return False
        return True

    def get_or_compile(
        self,
        engine: Engine,
        net: Union[BlockFFNN, Sequence[BSRLayer]],
        backend: Optional[str] = None,
        mesh: Optional[Mesh] = None,
    ) -> Tuple[Union[ExecutionPlan, ShardedExecutionPlan], bool]:
        """Warm-start compile: ``(plan, hit)``.

        Hit: rebuilt from the stored order(s), zero annealer iterations.
        Miss: full ``Engine.compile`` (schedule + CR — per shard when a
        ``mesh`` is given), then persisted so the next process is warm.

        Thread-safe: concurrent callers with the same key serialize on a
        per-key lock, so at most one of them pays the compile.
        """
        key = plan_cache_key(engine, net, mesh)
        with self._key_lock(key):
            with self.tracer.span("store.load", key=key[:12]) as sp:
                plan = self.load(engine, net, backend, mesh=mesh)
                sp["hit"] = plan is not None
            if plan is not None:
                return plan, True
            with self.tracer.span("store.compile", key=key[:12]):
                plan = engine.compile(net, backend, mesh=mesh)
                self.put(engine, plan)
            return plan, False
