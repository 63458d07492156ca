"""Mesh-resident sparse tables: row-sharded distributed lookup ON the mesh.

The PS path (``distributed/ps.py``) keeps huge embedding tables on
host-CPU servers and round-trips every batch's rows over TCP — the
right tool when a table exceeds the whole mesh's HBM, and the only tool
the runtime had until this module.  But the ``deepfm`` canonical layout
(``sharding/layouts.py``) already *declares* the better placement for
tables that fit the MESH (just not one chip): row-shard the id dim
across devices.  This module is the runtime for that declaration:

* the table lives as ONE jax array sharded ``P(axis, None)`` over the
  bound mesh — each device holds ``height / n_shards`` contiguous rows,
  so per-device table bytes are ~``1/n_shards`` of replicated and a
  table larger than one chip's HBM share becomes usable;
* lookup is a device-side gather under ``shard_map``: every shard
  gathers the rows it owns (ids outside its range contribute zeros)
  and a ``psum`` over the shard axis assembles the full row set on
  every device — the id→shard routing rides the mesh collectives
  (the all-to-all/psum pattern of ``parallel/hybrid.py``), replacing
  the host PS round-trip entirely;
* grads push back shard-wise: the same masked routing feeds a
  scatter-add update applied per shard with the SERVER-side optimizer
  semantics (``sgd`` / ``adagrad`` — numerically the ``ps._Table.push``
  kernels), so a mesh-resident table trains with loss parity against
  the PS path for deterministic initializers;
* ``row_dtype="int8"`` stores rows as int8 codes with per-row fp32
  absmax scales (``paddle_tpu.quant``) riding the SAME shard layout —
  ~4x fewer table bytes per device at the same shard count.  Lookup
  dequantizes after the local gather, BEFORE the psum (collectives
  move fp32 rows, tables store int8); push dequant-accumulates: the
  per-target-row aggregated grad is applied to the dequantized row and
  the result requantized, and the quantizer's fixed-point identity
  (``requantize(dequantize(q, s)) == (q, s)`` exactly) makes the
  row-set write collision-safe — every lane targeting a row writes the
  identical bytes, and untouched rows round-trip unchanged.  Adagrad
  moments stay fp32 (they are optimizer state, not capacity-bound
  serving state).

Unique-id counts are bucketed by the caller (the executor's prefetch
pads to a power-of-two ladder, or the autotuned
``propose_id_bucket_ladder`` rungs), and lookup/push executables are
built once per (table, bucket) — ``warmup()`` pre-compiles the ladder,
after which mixed batch sizes cost ZERO recompiles (``compiles`` is
the ground truth, same contract as ``Executor.jit_cache_stats``).

Bind with :func:`bind_mesh_tables` on a ``CompiledProgram`` whose mesh
carries the shard axis; the executor's
``_prefetch_distributed_tables`` then routes lookups/pushes here for
every bound table and never touches a ``PSClient`` for them.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.sharding import metrics as _sh_metrics

__all__ = ["MeshTable", "MeshTableRuntime", "bind_mesh_tables",
           "ROW_DTYPES", "normalize_row_dtype"]

ROW_DTYPES = ("fp32", "int8")


def normalize_row_dtype(row_dtype) -> str:
    """Canonicalize a table row storage dtype (``None`` -> ``fp32``;
    ``"float32"`` is accepted as an alias)."""
    d = str(row_dtype or "fp32").lower()
    if d == "float32":
        d = "fp32"
    if d not in ROW_DTYPES:
        raise ValueError(
            "mesh-table row_dtype %r not in %s" % (row_dtype, ROW_DTYPES))
    return d


class MeshTable:
    """One mesh-resident table: the sharded row array plus the
    server-optimizer state that rides with it (adagrad moments shard
    exactly like their rows).  ``row_dtype="int8"`` tables carry a
    per-row fp32 ``scales`` array sharded like the rows' id dim."""

    __slots__ = ("name", "dim", "height", "padded_height",
                 "rows_per_shard", "array", "moments", "row_dtype",
                 "scales")

    def __init__(self, name: str, dim: int, height: int,
                 padded_height: int, rows_per_shard: int,
                 array, moments=None, row_dtype: str = "fp32",
                 scales=None):
        self.name = name
        self.dim = int(dim)
        self.height = int(height)
        self.padded_height = int(padded_height)
        self.rows_per_shard = int(rows_per_shard)
        self.array = array
        self.moments = moments
        self.row_dtype = row_dtype
        self.scales = scales

    def bytes_per_device(self) -> int:
        """Addressable shard bytes of the row array (plus the int8
        scales, when present) on one device — the capacity number, from
        the STORED dtype: ~``1/n_shards`` of replicated, and ~4x less
        again for int8 rows."""
        shards = self.array.addressable_shards
        total = int(shards[0].data.nbytes) if shards else 0
        if self.scales is not None:
            sshards = self.scales.addressable_shards
            total += int(sshards[0].data.nbytes) if sshards else 0
        return total

    def replicated_bytes(self) -> int:
        total = int(self.array.nbytes)
        if self.scales is not None:
            total += int(self.scales.nbytes)
        return total


class MeshTableRuntime:
    """The lookup/push engine for a set of mesh-resident tables.

    Construction materializes every table of ``program`` (the
    ``_distributed_tables`` metadata the ``embedding(is_distributed=
    True)`` layer records) onto ``mesh``, row-sharded over ``axis``.
    ``optimizer``/``lr`` select the push-side update kernel — the same
    server-side semantics the PS applies (``sgd`` | ``adagrad``), so a
    program can move between the two backends without retuning.

    ``initializer="zeros"`` is bit-exact with a zero-initialized PS
    table (the parity configuration); ``"uniform"`` draws one seeded
    uniform(-0.05, 0.05) table up front — deterministic, but NOT
    row-parity with the PS's lazy per-id init order.
    """

    _OPTIMIZERS = ("sgd", "adagrad")

    def __init__(self, program, mesh, axis: str,
                 optimizer: str = "sgd", lr: float = 0.1,
                 initializer: str = "zeros", seed: int = 0,
                 row_dtype: str = "fp32"):
        if optimizer not in self._OPTIMIZERS:
            raise ValueError(
                "mesh-table optimizer %r not in %s"
                % (optimizer, self._OPTIMIZERS))
        if axis not in mesh.axis_names:
            raise ValueError(
                "mesh has no axis %r (axes: %s)"
                % (axis, list(mesh.axis_names)))
        metas = getattr(program, "_distributed_tables", None)
        if not metas:
            raise ValueError("program has no distributed lookup tables")
        self.mesh = mesh
        self.axis = axis
        self.optimizer = optimizer
        self.row_dtype = normalize_row_dtype(row_dtype)
        self.lr = float(lr)
        self.n_shards = int(dict(
            zip(mesh.axis_names, mesh.devices.shape))[axis])
        self.tables: Dict[str, MeshTable] = {}
        self.compiles = 0  # lookup/push executables built (recompile truth)
        self.lookups = 0
        self.pushes = 0
        self._fns: Dict[Any, Any] = {}  # (kind, table, bucket) -> jitted
        self._lock = threading.Lock()
        rng = np.random.RandomState(seed)
        seen = set()
        for meta in metas.values():
            name = meta["table"]
            if name in seen:  # tied embeddings share one table
                continue
            seen.add(name)
            self._materialize(name, int(meta["height"]), int(meta["dim"]),
                              initializer, rng)

    # ------------------------------------------------------------------
    def __contains__(self, table: str) -> bool:
        return table in self.tables

    def _materialize(self, name: str, height: int, dim: int,
                     initializer: str, rng) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        padded = -(-height // self.n_shards) * self.n_shards
        if padded >= 1 << 31:
            # lookup/push route ids as int32 on-device; a larger table
            # would silently wrap ids to the wrong shard row
            raise ValueError(
                "mesh table %r height %d exceeds the int32 id-routing "
                "range (2^31-1); shard across more meshes or keep it "
                "on the PS" % (name, height))
        if initializer == "zeros":
            host = np.zeros((padded, dim), np.float32)
        elif initializer == "uniform":
            host = rng.uniform(-0.05, 0.05, (padded, dim)).astype(np.float32)
        else:
            raise ValueError(
                "mesh-table initializer %r not in ('zeros', 'uniform')"
                % initializer)
        sh = NamedSharding(self.mesh, P(self.axis, None))
        scales = None
        if self.row_dtype == "int8":
            from paddle_tpu.quant import INT8_SCALE_FLOOR

            # host-side mirror of quant.quantize_rows (np.rint rounds
            # half-to-even like jnp.round, so the device push kernels
            # round-trip these exact codes)
            hs = np.maximum(
                np.max(np.abs(host), axis=1) / 127.0,
                INT8_SCALE_FLOOR).astype(np.float32)
            host = np.clip(np.rint(host / hs[:, None]),
                           -127, 127).astype(np.int8)
            scales = jax.device_put(
                hs, NamedSharding(self.mesh, P(self.axis)))
        arr = jax.device_put(host, sh)
        moments = None
        if self.optimizer == "adagrad":
            moments = jax.device_put(np.zeros((padded, dim), np.float32), sh)
        tbl = MeshTable(name, dim, height, padded, padded // self.n_shards,
                        arr, moments, row_dtype=self.row_dtype,
                        scales=scales)
        self.tables[name] = tbl
        _sh_metrics.SPARSE_TABLE_BYTES.labels(table=name).set(
            tbl.bytes_per_device())
        _sh_metrics.SPARSE_ROW_DTYPE.labels(
            table=name, dtype=self.row_dtype).set(1)

    # ------------------------------------------------------------------
    # Executable builders: one per (table, bucket) — warmup() walks the
    # ladder so steady-state traffic never compiles.
    # ------------------------------------------------------------------
    def _fn(self, kind: str, table: str, bucket: int):
        key = (kind, table, int(bucket))
        fn = self._fns.get(key)
        if fn is None:
            with self._lock:
                fn = self._fns.get(key)
                if fn is None:
                    build = (self._build_lookup if kind == "lookup"
                             else self._build_push)
                    fn = self._fns[key] = build(self.tables[table])
                    self.compiles += 1
        return fn

    def _build_lookup(self, tbl: MeshTable):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        rps = tbl.rows_per_shard

        if tbl.scales is not None:
            from paddle_tpu.quant import dequantize_rows

            def local_lookup(shard, scales, ids):
                # int8 rung: dequantize AFTER the local gather, BEFORE
                # the psum — the table stores int8, the collective
                # moves (and the step consumes) fp32 rows
                lo = jax.lax.axis_index(axis) * rps
                local = ids - lo
                ok = (local >= 0) & (local < rps)
                safe = jnp.clip(local, 0, rps - 1)
                rows = jnp.where(
                    ok[:, None],
                    dequantize_rows(shard[safe], scales[safe]), 0.0)
                return jax.lax.psum(rows, axis)

            smapped = jax.shard_map(
                local_lookup, mesh=self.mesh,
                in_specs=(P(axis, None), P(axis), P()), out_specs=P())
            return jax.jit(smapped)

        def local_lookup(shard, ids):
            # id→shard routing: each shard gathers the rows it owns and
            # zeros the rest; the psum assembles full rows everywhere
            # (the all-to-all/psum pattern of parallel/hybrid.py)
            lo = jax.lax.axis_index(axis) * rps
            local = ids - lo
            ok = (local >= 0) & (local < rps)
            safe = jnp.clip(local, 0, rps - 1)
            rows = jnp.where(ok[:, None], shard[safe], 0.0)
            return jax.lax.psum(rows, axis)

        smapped = jax.shard_map(
            local_lookup, mesh=self.mesh,
            in_specs=(P(axis, None), P()), out_specs=P())
        return jax.jit(smapped)

    def _build_push(self, tbl: MeshTable):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        rps = tbl.rows_per_shard
        lr = self.lr
        adagrad = self.optimizer == "adagrad"
        int8_rows = tbl.scales is not None

        def route(ids):
            # shard-wise routing, shared by both kernels: ids the shard
            # doesn't own scatter a zero (clip + mask), so each row
            # updates exactly once mesh-wide.  Padding dups (the
            # bucketed-unique trick repeats ids[0]) carry zero grads —
            # their scatter-add is a no-op, same as the PS.
            lo = jax.lax.axis_index(axis) * rps
            local = ids - lo
            ok = (local >= 0) & (local < rps)
            return ok, jnp.clip(local, 0, rps - 1)

        if int8_rows:
            from paddle_tpu.quant import dequantize_rows, quantize_rows

            # The int8 push is a row-SET, not a scatter-add: the update
            # must re-quantize whole rows (codes AND scale change
            # together).  An ``at[].set`` with duplicate indexes —
            # bucket-padding dups, clipped foreign ids — is only
            # deterministic when every colliding lane writes identical
            # bytes, so grads are first aggregated per TARGET row
            # (``same @ g``: lanes routed to one row all see the row's
            # total grad).  Lanes whose row took no grad write
            # ``requantize(dequantize(row))``, exact-identity by the
            # quantizer's fixed-point property — untouched rows keep
            # their bytes.
            if adagrad:
                def local_push(shard, scales, mom, ids, grads):
                    ok, safe = route(ids)
                    g = jnp.where(ok[:, None], grads, 0.0)
                    same = (safe[:, None] == safe[None, :]).astype(g.dtype)
                    m_row = mom[safe] + same @ (g * g)
                    mom = mom.at[safe].set(m_row)
                    g_row = same @ g
                    base = dequantize_rows(shard[safe], scales[safe])
                    nq, ns = quantize_rows(
                        base - lr * g_row / (jnp.sqrt(m_row) + 1e-6))
                    return (shard.at[safe].set(nq),
                            scales.at[safe].set(ns), mom)

                in_specs = (P(axis, None), P(axis), P(axis, None),
                            P(), P())
                out_specs = (P(axis, None), P(axis), P(axis, None))
                donate_args = (0, 1, 2)
            else:
                def local_push(shard, scales, ids, grads):
                    ok, safe = route(ids)
                    g = jnp.where(ok[:, None], grads, 0.0)
                    same = (safe[:, None] == safe[None, :]).astype(g.dtype)
                    g_row = same @ g
                    base = dequantize_rows(shard[safe], scales[safe])
                    nq, ns = quantize_rows(base - lr * g_row)
                    return shard.at[safe].set(nq), scales.at[safe].set(ns)

                in_specs = (P(axis, None), P(axis), P(), P())
                out_specs = (P(axis, None), P(axis))
                donate_args = (0, 1)
        elif adagrad:
            def local_push(shard, mom, ids, grads):
                # numerically ps._Table.push adagrad: m += g*g;
                # row -= lr*g/(sqrt(m)+1e-6), per unique id
                ok, safe = route(ids)
                g = jnp.where(ok[:, None], grads, 0.0)
                mom = mom.at[safe].add(g * g)
                denom = jnp.sqrt(mom[safe]) + 1e-6
                shard = shard.at[safe].add(
                    jnp.where(ok[:, None], -lr * g / denom, 0.0))
                return shard, mom

            in_specs = (P(axis, None), P(axis, None), P(), P())
            out_specs = (P(axis, None), P(axis, None))
            donate_args = (0, 1)
        else:
            def local_push(shard, ids, grads):
                # numerically ps._Table.push sgd: row -= lr*g
                ok, safe = route(ids)
                g = jnp.where(ok[:, None], grads, 0.0)
                return shard.at[safe].add(-lr * g)

            in_specs = (P(axis, None), P(), P())
            out_specs = P(axis, None)
            donate_args = (0,)

        smapped = jax.shard_map(
            local_push, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs)
        from paddle_tpu.executor import _donate_kwargs

        # donate the table/moment buffers so the update is in-place in
        # HBM (skipped on CPU — the persistent-cache aliasing hazard,
        # see executor._donate_kwargs)
        donate = _donate_kwargs(self.mesh.devices.flat[0])
        kwargs = ({"donate_argnums": donate_args} if donate else {})
        return jax.jit(smapped, **kwargs)

    # ------------------------------------------------------------------
    # hot-path: begin sparse_lookup (bucketed device gather + shard-wise
    # push dispatch; fn lookup is a dict hit after warmup and the jitted
    # calls are async — no blocking device sync in this region)
    def lookup(self, table: str, uniq_ids) -> Any:
        """Rows for the (bucketed) unique ids: [len(ids), dim] device
        array, replicated over the mesh — feed it straight into the
        compiled step (zero host round-trip)."""
        import jax.numpy as jnp

        tbl = self.tables[table]
        ids = jnp.asarray(uniq_ids, jnp.int32).reshape(-1)  # hot-ok: device-side cast, not a host sync
        fn = self._fn("lookup", table, ids.shape[0])
        self.lookups += 1
        _sh_metrics.SPARSE_LOOKUPS.inc()
        if tbl.scales is not None:
            return fn(tbl.array, tbl.scales, ids)
        return fn(tbl.array, ids)

    def push(self, table: str, uniq_ids, grads) -> None:
        """Apply the (bucketed) unique-id grads shard-wise with the
        bound optimizer.  ``grads`` may be a device array (the fetched
        rows-grad tail) — it never touches the host."""
        import jax.numpy as jnp

        tbl = self.tables[table]
        ids = jnp.asarray(uniq_ids, jnp.int32).reshape(-1)  # hot-ok: device-side cast, not a host sync
        fn = self._fn("push", table, ids.shape[0])
        if tbl.scales is not None:
            if tbl.moments is not None:
                tbl.array, tbl.scales, tbl.moments = fn(
                    tbl.array, tbl.scales, tbl.moments, ids, grads)
            else:
                tbl.array, tbl.scales = fn(
                    tbl.array, tbl.scales, ids, grads)
        elif tbl.moments is not None:
            tbl.array, tbl.moments = fn(tbl.array, tbl.moments, ids, grads)
        else:
            tbl.array = fn(tbl.array, ids, grads)
        self.pushes += 1
    # hot-path: end sparse_lookup

    # ------------------------------------------------------------------
    def warmup(self, buckets: Sequence[int], train: bool = True) -> int:
        """Pre-build lookup (and push, for training) executables for
        every table x bucket rung.  Returns the number of executables
        compiled; after this, traffic whose unique counts bucket into
        the ladder pays ZERO compiles (assert on ``compiles``)."""
        import jax

        before = self.compiles
        for name, tbl in self.tables.items():
            for b in sorted({int(b) for b in buckets}):
                rows = self.lookup(name, np.zeros(b, np.int64))
                jax.block_until_ready(rows)
                if train:
                    self.push(name, np.zeros(b, np.int64),
                              np.zeros((b, tbl.dim), np.float32))
        return self.compiles - before

    # ------------------------------------------------------------------
    def rows(self, table: str, ids) -> np.ndarray:
        """Host copy of specific rows (tests/checkpoint tooling; NOT the
        serving path — this one syncs)."""
        return np.asarray(self.lookup(table, np.asarray(ids)))

    # ------------------------------------------------------------------
    # checkpoint surface: the sharded row/moment arrays ride
    # TrainCheckpoint's shards/ path like any mesh-committed persistable
    # (paddle_tpu.faults.checkpoint gathers/restores through these two)
    # ------------------------------------------------------------------
    def checkpoint_state(self) -> Dict[str, Dict[str, Any]]:
        """``{entry name: {table, kind, array, height}}`` — every device
        array the runtime owns, named for a checkpoint manifest: the row
        array under the table's own name (kind ``mesh_table``) and the
        optimizer moments under ``<table>#moments`` (kind
        ``mesh_table_moments``).  Arrays are PADDED to the shard grid;
        ``height`` is the real row count — rows past it are never read
        by a lookup, so a restore may zero-fill them.  int8 tables add
        their per-row scales under ``<table>#scales`` (kind
        ``mesh_table_scales``): codes without scales decode to garbage,
        so the pair checkpoints and restores together."""
        out: Dict[str, Dict[str, Any]] = {}
        for name, tbl in sorted(self.tables.items()):
            out[name] = {"table": name, "kind": "mesh_table",
                         "array": tbl.array, "height": tbl.height}
            if tbl.scales is not None:
                out[name + "#scales"] = {
                    "table": name, "kind": "mesh_table_scales",
                    "array": tbl.scales, "height": tbl.height}
            if tbl.moments is not None:
                out[name + "#moments"] = {
                    "table": name, "kind": "mesh_table_moments",
                    "array": tbl.moments, "height": tbl.height}
        return out

    def install_state(self, table: str, kind: str, array) -> None:
        """Swap in a restored device array for ``table``'s rows or
        moments.  The array must already be placed with the table's own
        sharding/shape (the checkpoint restore re-places shard-wise onto
        this runtime's mesh before calling)."""
        tbl = self.tables[table]
        if kind == "mesh_table":
            target = tbl.array
        elif kind == "mesh_table_moments":
            target = tbl.moments
        elif kind == "mesh_table_scales":
            target = tbl.scales
        else:
            raise ValueError("unknown mesh-table state kind %r" % kind)
        if target is None:
            raise ValueError(
                "restored %s for table %r but the runtime holds no such "
                "state (row_dtype=%r, optimizer=%r)"
                % (kind, table, tbl.row_dtype, self.optimizer))
        if tuple(array.shape) != tuple(target.shape):
            raise ValueError(
                "restored %s for table %r has shape %s but the runtime "
                "holds %s" % (kind, table, tuple(array.shape),
                              tuple(target.shape)))
        if np.dtype(array.dtype) != np.dtype(target.dtype):
            raise ValueError(
                "restored %s for table %r has dtype %s but the runtime "
                "holds %s — the checkpoint was written under a "
                "different row_dtype; rebind with the matching one"
                % (kind, table, np.dtype(array.dtype),
                   np.dtype(target.dtype)))
        if kind == "mesh_table":
            tbl.array = array
        elif kind == "mesh_table_moments":
            tbl.moments = array
        else:
            tbl.scales = array

    def stats(self) -> Dict[str, Any]:
        return {
            "n_shards": self.n_shards,
            "axis": self.axis,
            "optimizer": self.optimizer,
            "row_dtype": self.row_dtype,
            "compiles": self.compiles,
            "lookups": self.lookups,
            "pushes": self.pushes,
            "tables": {
                name: {
                    "height": t.height,
                    "dim": t.dim,
                    "row_dtype": t.row_dtype,
                    "bytes_per_device": t.bytes_per_device(),
                    "replicated_bytes": t.replicated_bytes(),
                }
                for name, t in self.tables.items()
            },
        }

    def close(self) -> None:
        """Retire the per-table gauge series and drop the device state."""
        for name, tbl in self.tables.items():
            _sh_metrics.SPARSE_TABLE_BYTES.remove_labels(table=name)
            _sh_metrics.SPARSE_ROW_DTYPE.remove_labels(
                table=name, dtype=tbl.row_dtype)
        self.tables.clear()
        self._fns.clear()


def bind_mesh_tables(compiled, axis: Optional[str] = None,
                     optimizer: str = "sgd", lr: float = 0.1,
                     initializer: str = "zeros",
                     seed: int = 0,
                     row_dtype: str = "fp32") -> MeshTableRuntime:
    """Materialize ``compiled``'s distributed lookup tables ON its mesh,
    row-sharded over ``axis`` (default: the mesh's first axis), and
    attach the runtime so the executor's prefetch path routes every
    bound table through device-side gathers instead of host PS pulls.

    Requires a ``CompiledProgram``: the lookup results are
    mesh-replicated device arrays, which only a jit bound to the SAME
    mesh can consume — running the program uncompiled afterwards is a
    typed error at prefetch time, not a jax device mismatch.  The rows
    feed is registered mesh-REPLICATED (its leading dim is unique ids,
    not batch), while the id/label feeds keep the normal batch
    sharding.  Returns the runtime (also at ``program._mesh_tables``).

    ``row_dtype="int8"`` stores rows quantized (per-row absmax scales)
    for ~4x fewer table bytes per device — lookups still hand the step
    fp32 rows, so the consuming program is unchanged.
    """
    if not getattr(compiled, "_is_compiled_program", False):
        raise ValueError(
            "bind_mesh_tables needs a CompiledProgram (the mesh the "
            "tables shard over is the one the step runs on); wrap the "
            "program with CompiledProgram(prog).with_mesh(...) first")
    program = compiled._program
    mesh = compiled.mesh  # the tables MUST live where the step runs
    axis = axis or mesh.axis_names[0]
    runtime = MeshTableRuntime(
        program, mesh, axis, optimizer=optimizer, lr=lr,
        initializer=initializer, seed=seed, row_dtype=row_dtype)
    program._mesh_tables = runtime
    # the prefetched-rows feeds replicate (leading dim = unique ids);
    # everything else keeps the compiled program's batch sharding
    replicated = getattr(compiled, "_replicated_feeds", None)
    if replicated is None:
        replicated = compiled._replicated_feeds = set()
    for meta in program._distributed_tables.values():
        replicated.add(meta["rows_name"])
    return runtime
