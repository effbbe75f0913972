"""ClientStore: an O(sampled) client-state/data API for million-client rounds.

The paper's central scalability claim is that FedSDD's server cost
decouples from the client count C — but a server that holds a dense
``list[PyTree]`` of SCAFFOLD controls over ALL clients, or eagerly
materializes every client's data shard, is still O(C) in *memory* no
matter how fast its round loop is.  This module makes per-client state
and data an explicit API with two implementations:

  * ``InMemoryStore`` — today's behavior, the parity oracle: dense
    control list, every shard reachable, a bounded LRU of device rows /
    bucket stacks (what used to be the engine's bolt-on ``data_cache``
    dict).
  * ``SpillingStore`` — only *touched* clients are resident.  SCAFFOLD
    controls live in an LRU hot set whose evictions spill through
    ``fedckpt`` (one npz per client, ``load_pytree``-restorable across a
    process restart); untouched clients are implicitly the zero control,
    so C=1M costs nothing until round t samples a client.  Data rows use
    the same LRU device tier; evicted rows spill their npz once and
    reload from disk (or regenerate from the task — lazy ``client_data``
    sequences build shards on first touch).  The global SCAFFOLD control
    is maintained as a *running sum* (``sum += c_new - c_old`` at every
    ``put_control``), so ``control_mean()`` is O(1) in C instead of a
    dense O(C) reduction.

Both engines (``core/fedsdd`` sequential + vectorized ops, the
``core/engine`` bucket/plan path) route all per-client access through
``FedState.store``.  The LRU capacity is the
``FedConfig(client_cache_buckets=...)`` knob.
"""
from __future__ import annotations

import os
import tempfile
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

DEFAULT_CACHE_BUCKETS = 64


def resolve_cache_buckets(configured: Optional[int] = None) -> int:
    """The store's LRU capacity: the ``FedConfig(client_cache_buckets=...)``
    knob, defaulted.  (The legacy ``REPRO_ENGINE_CACHE_BUCKETS`` env
    override shipped its scheduled removal.)"""
    return DEFAULT_CACHE_BUCKETS if configured is None else int(configured)


def _num_examples(ds) -> int:
    if isinstance(ds, tuple):
        return len(ds[0])
    if isinstance(ds, dict):
        return len(next(iter(ds.values())))
    return len(ds)


def _tree_nbytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


class _LRU:
    """Insertion-ordered dict LRU with per-client pinning.

    Keys are ``(kind, cid_or_cids, n_pad)`` tuples; eviction skips
    entries whose client(s) are pinned by an open ``SampledView`` (a
    round in flight must never lose its own rows mid-round).  When every
    entry is pinned the cache is allowed to exceed capacity rather than
    evict live state.
    """

    def __init__(self, capacity: int,
                 on_evict: Optional[Callable[[tuple, Any], None]] = None):
        self.capacity = int(capacity)
        self.on_evict = on_evict
        self._d: dict = {}
        self._pins: dict[int, int] = {}     # cid -> pin count

    def get(self, key):
        if key in self._d:
            self._d[key] = self._d.pop(key)      # move to newest
            return self._d[key]
        return None

    def put(self, key, value):
        self._d.pop(key, None)                   # re-put refreshes recency
        self._d[key] = value
        self._shrink()
        return value

    def _pinned(self, key) -> bool:
        cids = key[1] if isinstance(key[1], tuple) else (key[1],)
        return any(c in self._pins for c in cids)

    def _shrink(self) -> None:
        while len(self._d) > self.capacity:
            victim = next((k for k in self._d if not self._pinned(k)), None)
            if victim is None:
                return                            # everything pinned: grow
            value = self._d.pop(victim)
            if self.on_evict is not None:
                self.on_evict(victim, value)

    def pin(self, cids) -> None:
        for c in cids:
            self._pins[int(c)] = self._pins.get(int(c), 0) + 1

    def unpin(self, cids) -> None:
        for c in cids:
            c = int(c)
            n = self._pins.get(c, 0) - 1
            if n <= 0:
                self._pins.pop(c, None)
            else:
                self._pins[c] = n
        self._shrink()

    def keys(self):
        return list(self._d)

    def values(self):
        return list(self._d.values())

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d


class SampledView:
    """A round-scoped window onto the store: the sampled cids' rows are
    pinned in the device tier for the view's lifetime (so a round's own
    bucket rows can't be evicted under it), and per-client reads go
    through the same store API.  Close (or use as a context manager)
    when the round's device programs have consumed the data."""

    def __init__(self, store: "ClientStore", cids):
        self.store = store
        self.cids = [int(c) for c in cids]
        self._open = True
        store._data.pin(self.cids)

    def get_data(self, cid: int, n_pad: int) -> PyTree:
        return self.store.get_data(cid, n_pad)

    def controls(self, cids=None) -> list[PyTree]:
        return [self.store.get_control(int(c))
                for c in (self.cids if cids is None else cids)]

    def close(self) -> None:
        if self._open:
            self._open = False
            self.store._data.unpin(self.cids)

    def __enter__(self) -> "SampledView":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ClientStore:
    """Per-client state/data access for the federated server.

    Subclasses implement the control tier (``get_control`` /
    ``put_control`` / ``control_mean``); the device data tier (padded
    rows + stacked bucket shards behind one LRU) is shared — it is the
    engine's old per-client row cache, promoted from bolt-on to API.
    """

    def __init__(self, task, capacity: Optional[int] = None):
        self.task = task
        self.capacity = resolve_cache_buckets(capacity)
        self._data = _LRU(self.capacity, on_evict=self._on_data_evict)
        self._zero: Optional[PyTree] = None     # zero-control template

    # ------------------------------------------------------- data tier
    @property
    def num_clients(self) -> int:
        return len(self.task.client_data)

    def client_shard(self, cid: int):
        """The raw host-side shard (lazy ``client_data`` sequences
        generate it on first touch)."""
        return self.task.client_data[int(cid)]

    def num_examples(self, cid: int) -> int:
        """|X_i| without forcing shard materialization when the task's
        ``client_data`` knows sizes a priori (``LazyClientData``)."""
        data = self.task.client_data
        if hasattr(data, "num_examples"):
            return int(data.num_examples(int(cid)))
        return _num_examples(data[int(cid)])

    def _build_row(self, cid: int, n_pad: int) -> PyTree:
        ds = self.client_shard(cid)
        n = _num_examples(ds)
        full = self.task.make_batch(ds, np.arange(n))
        return jax.tree.map(
            lambda x: jnp.asarray(np.concatenate(
                [np.asarray(x),
                 np.zeros((n_pad - n,) + x.shape[1:], np.asarray(x).dtype)])
                if n < n_pad else np.asarray(x)), full)

    def get_data(self, cid: int, n_pad: int) -> PyTree:
        """One client's full shard as a device-resident (n_pad, ...) row.

        Cached per (cid, n_pad) — the round-stable unit: bucket
        compositions churn (group reshuffles, the overlap executor's
        group split) but a client's padded row never does, so the
        host→device upload happens once per client, not once per bucket
        composition.
        """
        key = ("row", int(cid), int(n_pad))
        hit = self._data.get(key)
        if hit is not None:
            return hit
        row = self._restore_row(int(cid), int(n_pad))
        if row is None:
            row = self._build_row(int(cid), int(n_pad))
        return self._data.put(key, row)

    def get_bucket(self, cids: Sequence[int], n_pad: int) -> PyTree:
        """Device-resident (Cb, n_pad, ...) stack of full client shards.
        A bucket miss assembles the stack from cached per-client device
        rows — a device-side copy, not a host re-upload."""
        key = ("bucket", tuple(int(c) for c in cids), int(n_pad))
        hit = self._data.get(key)
        if hit is not None:
            return hit
        rows = [self.get_data(int(c), int(n_pad)) for c in cids]
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        # a miss is synchronous (rows are built through the host): ending
        # it with the stack written lets the device release the rows and
        # uploads the miss evicted before the round's programs allocate
        # their outputs on top of them
        jax.block_until_ready(stack)
        return self._data.put(key, stack)

    def sampled_view(self, cids) -> SampledView:
        """Pin this round's sampled clients resident and hand back a
        round-scoped accessor — the contract that makes server residency
        O(sampled): only viewed clients are guaranteed hot."""
        return SampledView(self, cids)

    # hooks the spilling subclass overrides ---------------------------------
    def _on_data_evict(self, key: tuple, value: PyTree) -> None:
        pass                                    # in-memory: just drop

    def _restore_row(self, cid: int, n_pad: int) -> Optional[PyTree]:
        return None

    # ---------------------------------------------------- control tier
    def init_controls(self, like: PyTree) -> None:
        """Record the zero-control template (SCAFFOLD c_i ≡ 0 at init)."""
        raise NotImplementedError

    @property
    def has_controls(self) -> bool:
        return self._zero is not None

    def get_control(self, cid: int) -> PyTree:
        raise NotImplementedError

    def put_control(self, cid: int, c: PyTree) -> None:
        raise NotImplementedError

    def control_mean(self) -> PyTree:
        """The server control c = mean_i c_i over ALL clients (untouched
        clients count as zero)."""
        raise NotImplementedError

    # ------------------------------------------- crash-safe resume hooks
    def flush(self) -> None:
        """Persist any volatile tiers so a fresh store over the same
        backing can reconstruct this one (no-op for stores whose state
        has nowhere durable to go)."""

    @property
    def control_sum(self) -> Optional[PyTree]:
        """The running f32 Σ_i c_i when the store maintains one (the
        spilling store's O(1) ``control_mean`` accumulator) — checkpointed
        verbatim because an incrementally-maintained fp sum differs in
        rounding from one rebuilt file-by-file at restart."""
        return None

    def set_control_sum(self, csum: PyTree) -> None:
        """Adopt a checkpointed running control sum (no-op when the store
        keeps no such accumulator)."""

    # ------------------------------------------------------- accounting
    def nbytes(self) -> int:
        """Resident client-state bytes: cached device rows/buckets plus
        whatever control state the subclass keeps hot.  THE scalability
        gauge: flat in C for the spilling store, O(C) for the dense one."""
        return sum(_tree_nbytes(v) for v in self._data.values()) \
            + self._control_nbytes()

    def _control_nbytes(self) -> int:
        return 0


class InMemoryStore(ClientStore):
    """Today's behavior as the parity oracle: a dense control list over
    all C clients and ``control_mean`` as the same ``sum(xs)/len(xs)``
    dense reduction the runner used to inline — bit-identical results,
    O(C) resident memory."""

    def __init__(self, task, capacity: Optional[int] = None):
        super().__init__(task, capacity)
        self._controls: Optional[list[PyTree]] = None

    def init_controls(self, like: PyTree) -> None:
        from repro.utils.pytree import tree_zeros_like
        self._zero = tree_zeros_like(like)
        self._controls = [self._zero for _ in range(self.num_clients)]

    def get_control(self, cid: int) -> PyTree:
        return self._controls[int(cid)]

    def put_control(self, cid: int, c: PyTree) -> None:
        self._controls[int(cid)] = c

    def control_mean(self) -> PyTree:
        cs = self._controls
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *cs)

    def _control_nbytes(self) -> int:
        if self._controls is None:
            return 0
        # zero templates are shared references until first put; count
        # distinct buffers once so nbytes reflects actual residency
        seen, total = set(), 0
        for c in self._controls:
            if id(c) not in seen:
                seen.add(id(c))
                total += _tree_nbytes(c)
        return total


class SpillingStore(ClientStore):
    """O(sampled) residency: touched clients live in LRU hot sets, spills
    go through ``fedckpt`` (one ``.npz`` per client), untouched clients
    are implicitly zero.  A new ``SpillingStore`` over the same directory
    restores every spilled control (the simulated-restart contract); data
    rows restore from their spill or regenerate from the task."""

    DATA_KIND = "data"
    CTRL_KIND = "ctrl"

    def __init__(self, task, capacity: Optional[int] = None,
                 directory: Optional[str] = None):
        super().__init__(task, capacity)
        self.directory = directory or tempfile.mkdtemp(
            prefix="repro-client-store-")
        os.makedirs(self.directory, exist_ok=True)
        self._ctrl_hot = _LRU(self.capacity, on_evict=self._on_ctrl_evict)
        self._ctrl_sum: Optional[PyTree] = None  # running Σ_i c_i (f32)
        self._row_like: dict[tuple, PyTree] = {}  # (cid, n_pad) -> shape spec

    # ------------------------------------------------------- data spill
    def _data_path(self, cid: int, n_pad: int) -> str:
        from repro.fedckpt.checkpointer import client_state_path
        return client_state_path(self.directory, self.DATA_KIND, cid,
                                 suffix=f"_n{n_pad}")

    def _on_data_evict(self, key: tuple, value: PyTree) -> None:
        kind = key[0]
        if kind != "row":
            return                               # bucket stacks: rebuildable
        from repro.fedckpt.checkpointer import save_pytree
        cid, n_pad = key[1], key[2]
        path = self._data_path(cid, n_pad)
        self._row_like[(cid, n_pad)] = jax.eval_shape(lambda: value)
        if not os.path.exists(path):             # spill once; rows are
            save_pytree(path, value)             # immutable across rounds

    def _restore_row(self, cid: int, n_pad: int) -> Optional[PyTree]:
        like = self._row_like.get((cid, n_pad))
        path = self._data_path(cid, n_pad)
        if like is None or not os.path.exists(path):
            return None                          # regenerate from the task
        from repro.fedckpt.checkpointer import load_pytree
        return load_pytree(path, like)

    # ---------------------------------------------------- control spill
    def _ctrl_path(self, cid: int) -> str:
        from repro.fedckpt.checkpointer import client_state_path
        return client_state_path(self.directory, self.CTRL_KIND, cid)

    def _on_ctrl_evict(self, key: tuple, value: PyTree) -> None:
        from repro.fedckpt.checkpointer import save_pytree
        save_pytree(self._ctrl_path(key[1]), value)

    def init_controls(self, like: PyTree) -> None:
        from repro.fedckpt.checkpointer import load_pytree, spilled_client_ids
        from repro.utils.pytree import tree_zeros_like
        self._zero = tree_zeros_like(like)
        f32_zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                like)
        self._ctrl_sum = f32_zero
        # simulated-restart recovery: controls spilled by a previous
        # process over this directory re-enter the running sum
        for cid in spilled_client_ids(self.directory, self.CTRL_KIND):
            c = load_pytree(self._ctrl_path(cid), self._zero)
            self._ctrl_sum = jax.tree.map(
                lambda s, x: s + x.astype(jnp.float32), self._ctrl_sum, c)

    def get_control(self, cid: int) -> PyTree:
        cid = int(cid)
        hit = self._ctrl_hot.get(("ctrl", cid))
        if hit is not None:
            return hit
        path = self._ctrl_path(cid)
        if os.path.exists(path):
            from repro.fedckpt.checkpointer import load_pytree
            return self._ctrl_hot.put(("ctrl", cid),
                                      load_pytree(path, self._zero))
        return self._zero                        # never touched

    def put_control(self, cid: int, c: PyTree) -> None:
        cid = int(cid)
        old = self.get_control(cid)
        self._ctrl_sum = jax.tree.map(
            lambda s, new, prev: s + new.astype(jnp.float32)
            - prev.astype(jnp.float32), self._ctrl_sum, c, old)
        self._ctrl_hot.put(("ctrl", cid), c)

    def control_mean(self) -> PyTree:
        n = self.num_clients
        return jax.tree.map(lambda s, z: (s / n).astype(z.dtype),
                            self._ctrl_sum, self._zero)

    # ------------------------------------------- crash-safe resume hooks
    def flush(self) -> None:
        """Spill every HOT control to disk without evicting it: after a
        flush, a fresh ``SpillingStore`` over the same directory sees the
        exact control set this one holds — what the full-state checkpoint
        calls at a round boundary so a kill loses nothing."""
        from repro.fedckpt.checkpointer import save_pytree
        for key in self._ctrl_hot.keys():
            save_pytree(self._ctrl_path(key[1]), self._ctrl_hot.get(key))

    @property
    def control_sum(self) -> Optional[PyTree]:
        return self._ctrl_sum

    def set_control_sum(self, csum: PyTree) -> None:
        self._ctrl_sum = csum

    def _control_nbytes(self) -> int:
        total = sum(_tree_nbytes(v) for v in self._ctrl_hot.values())
        if self._ctrl_sum is not None:
            total += _tree_nbytes(self._ctrl_sum)
        return total


def make_client_store(cfg, task) -> ClientStore:
    """Build the configured store (``FedConfig.client_store``)."""
    if cfg.client_store == "spilling":
        return SpillingStore(task, capacity=cfg.client_cache_buckets,
                             directory=cfg.client_store_dir)
    return InMemoryStore(task, capacity=cfg.client_cache_buckets)
