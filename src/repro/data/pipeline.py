"""Client data pipeline: per-round sampling + the vectorized chunk stager.

The execution engine consumes data in CHUNKS of rounds: one gather per
data field produces the whole ``(n_rounds, C, steps, b, ...)`` batch
tensor a ``per_round_batch`` scan needs, replacing the per-client/
per-round Python staging loops. Index computation is host-side numpy
(cheap); the gather touches the actual sample arrays exactly once per
chunk.

WHERE THE GATHER RUNS: each ``SimulationEngine.run`` places the
shared sample store for its chunks (``place_store``). Where staging's
whole device footprint (``staging_bytes``: the store, the chunks in
flight and one gather's scratch) fits a quarter of the device's memory,
the store lives on the device (``DeviceStore``), and staging uploads a
chunk's indices and gathers there: the samples never cross the host
again, only the int32 indices do. Otherwise, and where the backend
reports no memory (the CPU), the store stays a numpy dict and
``gather_chunk`` fancy-gathers it on the host, for the engine to copy
to the device. Both paths hand the round the same values bit for bit.

THE STAGING CONTRACT (mirrors the ``Environment`` schedule contract):
round t's batch indices are a pure function of (seed, t, selected[t]) —
``stage_chunk(t0, n)`` row i is bit-identical to staging round t0+i on
its own. Chunked execution, the per-round fallback and a resumed run
therefore all see the same sample stream.

``ChunkPrefetcher`` overlaps staging with device execution: a single
worker thread stages chunk k+1 while chunk k runs on device (depth-1
double buffering, so stateful environments are never entered
concurrently). From a device store the worker only dispatches the
gather, which the device runs after chunk k.

``partition_plan`` is the staging half of the PARTITIONED client plane
(``fl.client_plane``): it groups each round's cohorts by FES
limited-ness into static-width dispatch/scatter index arrays that ride
the schedule dict into the compiled round.
"""
from __future__ import annotations

import math
import queue
import threading

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def sample_shard_steps(indices: np.ndarray, rng: np.random.RandomState,
                       steps: int, batch_size: int) -> np.ndarray:
    """(steps, batch) global indices from one shard, reshuffled-epoch
    order — THE sampling algorithm, shared by the dense ``ClientDataset``
    list and the K-free ``VirtualClientShards`` so both draw
    bit-identical streams from identical shard index arrays."""
    n = len(indices)
    need = steps * batch_size
    reps = int(np.ceil(need / max(n, 1)))
    idx = np.concatenate([rng.permutation(indices) for _ in range(reps)])
    return idx[:need].reshape(steps, batch_size)


class ClientDataset:
    """One client's local shard with epoch-style batch sampling."""

    def __init__(self, data: dict, indices: np.ndarray):
        self.data = data
        self.indices = np.asarray(indices)

    def __len__(self):
        return len(self.indices)

    def sample_step_indices(self, rng: np.random.RandomState, steps: int,
                            batch_size: int) -> np.ndarray:
        """(steps, batch) GLOBAL sample indices, reshuffled-epoch order."""
        return sample_shard_steps(self.indices, rng, steps, batch_size)

    def sample_steps(self, rng: np.random.RandomState, steps: int,
                     batch_size: int):
        """(steps, batch, ...) arrays, sampling with reshuffled epochs."""
        idx = self.sample_step_indices(rng, steps, batch_size)
        return {k: v[idx] for k, v in self.data.items()}


def build_clients(data: dict, partition: list[np.ndarray]) -> list[ClientDataset]:
    return [ClientDataset(data, idx) for idx in partition]


class VirtualClientShards:
    """K clients over ONE base store with no per-client objects — the
    staging half of a virtual population (``repro.env.virtual``).

    A single base permutation (drawn once from the staging seed, off the
    round axis) defines every shard arithmetically: client i owns
    ``order[(i * shard_size + j) % n]`` for j < shard_size. Client i's
    shard is therefore a pure function of (i, seed) — nothing is
    materialised per client, so K = 10^6 costs the same as K = 20. Once
    K * shard_size exceeds the base store the shards overlap by wrapping
    around the permutation (distinct clients still hold distinct,
    deterministic index sets — the standard trick for simulating
    populations far larger than the benchmark corpus).

    Duck-type contract with ``list[ClientDataset]`` where the engine and
    stager need it: ``len``, ``.data``, and per-client index sampling —
    dispatch is on the ``shard_indices`` attribute.
    """

    def __init__(self, data: dict, num_clients: int,
                 shard_size: int | None = None, seed: int = 0):
        self.data = data
        self.num_clients = int(num_clients)
        self.n = len(next(iter(data.values())))
        if shard_size is None:
            shard_size = max(1, self.n // self.num_clients)
        self.shard_size = int(shard_size)
        assert 0 < self.shard_size <= self.n, (self.shard_size, self.n)
        self.order = np.random.RandomState(
            (seed + 0xA5F152) % 2**32).permutation(self.n)

    def __len__(self):
        return self.num_clients

    @property
    def min_size(self) -> int:
        return self.shard_size

    def shard_indices(self, i: int) -> np.ndarray:
        start = (int(i) * self.shard_size) % self.n
        return self.order[(start + np.arange(self.shard_size)) % self.n]

    def sample_step_indices(self, i: int, rng: np.random.RandomState,
                            steps: int, batch_size: int) -> np.ndarray:
        return sample_shard_steps(self.shard_indices(i), rng, steps,
                                  batch_size)

    def client_sizes(self, selected: np.ndarray) -> np.ndarray:
        """|D_i| aggregation weights — the ``data_sizes`` callable the
        environment layer consumes (``env.resolve(fl, data_sizes=...)``)."""
        return np.full(np.shape(selected), self.shard_size, np.float32)


# --------------------------------------------------------------------------
# chunked staging (the engine's data plane)
# --------------------------------------------------------------------------

def stage_rng(seed: int, t: int) -> np.random.RandomState:
    """Round t's batch-sampling stream — independent per round, keyed on
    the absolute round index (cf. ``env.base.round_rng``), so staging is
    pure in t and survives chunking/resume unchanged."""
    return np.random.RandomState(
        (seed * 1_000_003 + t + 0x51ED270) % 2**32)


def stage_round_indices(clients, selected: np.ndarray,
                        seed: int, t: int, steps: int,
                        batch_size: int) -> np.ndarray:
    """(C, steps, batch) global indices for round t's selected clients.

    ``clients`` is either the dense ``list[ClientDataset]`` or a
    ``VirtualClientShards``; both consume the shared per-round stream in
    selected order, so a dense list built from ``shards.shard_indices``
    stages bit-identical batches. Cost is O(C x steps x batch) either
    way — never O(K)."""
    rng = stage_rng(seed, t)
    if hasattr(clients, "shard_indices"):
        return np.stack([clients.sample_step_indices(int(i), rng, steps,
                                                     batch_size)
                         for i in selected])
    return np.stack([clients[int(i)].sample_step_indices(rng, steps,
                                                         batch_size)
                     for i in selected])


def stage_chunk_indices(clients, selected: np.ndarray, seed: int,
                        t0: int, steps: int,
                        batch_size: int) -> np.ndarray:
    """The index draw of ``stage_chunk``: (n_rounds, C, steps, batch)
    global sample indices, row i drawn as round ``t0 + i`` alone."""
    selected = np.asarray(selected)
    return np.stack([stage_round_indices(clients, selected[i], seed, t0 + i,
                                         steps, batch_size)
                     for i in range(selected.shape[0])])


def gather_chunk(data: dict, idx: np.ndarray) -> dict:
    """The host gather of ``stage_chunk``: ONE numpy fancy-gather per
    data field, {field: (*idx.shape, ...)}."""
    return {k: v[idx] for k, v in data.items()}


def store_fits_device(nbytes: int, bytes_limit: int | None) -> bool:
    """THE placement rule of the sample store: on the device when
    staging's device footprint (``nbytes``, from ``staging_bytes``)
    takes at most a quarter of the device's memory, which leaves three
    quarters to the model, its state and the round program. A backend
    that reports no memory (the CPU, whose device memory is host
    memory) keeps the host gather: nothing there says the store fits,
    and a host "device" copy saves no transfer."""
    return bytes_limit is not None and nbytes <= bytes_limit // 4


def device_bytes_limit(devices) -> int | None:
    """The smallest ``bytes_limit`` of ``devices``; None when any of
    them reports no memory stats."""
    stats = [d.memory_stats() for d in devices]
    if any(not s or "bytes_limit" not in s for s in stats):
        return None
    return min(int(s["bytes_limit"]) for s in stats)


#: a device store keeps each sample as one row, padded to a multiple of
#: this many elements: a TPU's compact layout makes the minor axis the
#: one that pads least, so an unpadded (N, 784) store would keep the
#: SAMPLE axis minor and every gather would first transpose it whole
#: (measured on a v5e: 9.8 ms a chunk of the paper CNN, 3.5 ms padded,
#: 83 ms gathering (N, 28, 28, 1) as it is)
ROW_ALIGN = 128


def _row_bytes(v: np.ndarray, padded: bool) -> int:
    width = math.prod(v.shape[1:])
    if padded and v.ndim >= 2:
        width += -width % ROW_ALIGN
    return width * v.dtype.itemsize


def staging_bytes(data: dict, chunk_samples: int, in_flight: int) -> int:
    """The device bytes staging holds at once from a device store of
    ``data``: the store (one padded row a sample), ``in_flight`` staged
    chunks of ``chunk_samples`` samples, and one chunk of padded rows,
    the gather's scratch before it cuts them back. A chunk is counted
    whole on every device, though a mesh shards it over its cohorts."""
    fields = [np.asarray(v) for v in data.values()]
    n = len(fields[0])
    row = sum(_row_bytes(v, padded=False) for v in fields)
    padded = sum(_row_bytes(v, padded=True) for v in fields)
    return n * padded + chunk_samples * (in_flight * row + padded)


def _as_rows(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v)
    if v.ndim < 2:
        return v
    rows = v.reshape(len(v), -1)
    return np.pad(rows, ((0, 0), (0, -rows.shape[1] % ROW_ALIGN)))


def _take_rows(fields: dict, idx, shapes: dict, mesh) -> dict:
    out = {}
    for k, v in fields.items():
        rows = v.at[idx].get(mode="promise_in_bounds")
        if shapes[k]:
            rows = rows[..., :math.prod(shapes[k])].reshape(
                idx.shape + shapes[k])
        out[k] = rows
    if mesh is None:
        return out
    # the stacked client axis (dim 1) over the mesh's "client" axis, as
    # make_round_step constrains the batch, so the round needs no
    # reshard; replicated where the axis does not divide the cohorts
    spec = (P(None, "client") if idx.shape[1] % mesh.shape["client"] == 0
            else P())
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))


class DeviceStore:
    """The shared sample store held on the device (replicated over
    ``mesh``, when given), one padded row a sample (``ROW_ALIGN``).
    ``gather(idx)`` uploads the chunk's indices and dispatches one
    gather program for every field, which cuts each row back to the
    sample's shape; it returns the device arrays without waiting for
    them."""

    def __init__(self, data: dict, mesh=None):
        self.n = len(next(iter(data.values())))
        sharding = None if mesh is None else NamedSharding(mesh, P())
        self.fields = {k: jax.device_put(_as_rows(v), sharding)
                       for k, v in data.items()}
        shapes = {k: np.shape(v)[1:] for k, v in data.items()}

        def device_gather(fields, idx):
            return _take_rows(fields, idx, shapes, mesh)
        self._gather = jax.jit(device_gather)

    def gather(self, idx: np.ndarray) -> dict:
        # the device gather is told its indices are in bounds: hold them
        # to what the host gather would accept
        if idx.size and not -self.n <= idx.min() <= idx.max() < self.n:
            raise IndexError(f"sample indices outside a store of {self.n}")
        return self._gather(self.fields, np.asarray(idx, np.int32))


def place_store(data: dict, chunk_samples: int, in_flight: int,
                mesh=None, placed=None):
    """Where staging gathers chunks of ``chunk_samples`` samples from,
    ``in_flight`` of them held at once: a ``DeviceStore`` of ``data``
    when ``store_fits_device`` on every device that holds it (the
    mesh's, else the default device), else ``data`` itself (host
    gather). ``placed``, a ``DeviceStore`` of ``data`` placed before,
    is returned where it still fits instead of uploading again."""
    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.devices()[:1])
    need = staging_bytes(data, chunk_samples, in_flight)
    if not store_fits_device(need, device_bytes_limit(devices)):
        return data
    return placed if placed is not None else DeviceStore(data, mesh)


def stage_chunk(store, clients,
                selected: np.ndarray, seed: int, t0: int, steps: int,
                batch_size: int, *, timer=None) -> dict:
    """Stage a whole chunk of rounds with ONE gather per data field.

    ``store``: a ``DeviceStore`` (device gather) or the numpy dict of
    samples (host gather); ``place_store`` chooses. selected:
    (n_rounds, C) client indices (``Environment.batch`` rows). Returns
    {field: (n_rounds, C, steps, batch, ...)} arrays — device arrays
    still being computed from a device store, numpy arrays from the
    host — exactly the ``per_round_batch`` layout ``make_train_loop``
    scans over. Row i is bit-identical to staging round ``t0 + i``
    alone, on either path. A ``timer`` (``obs.timing.PhaseTimes``)
    books the gather as the phase ``stage_gather`` (on the device path:
    the index upload and the dispatch), and a device gather also as a
    call of the counter ``stage_device`` (zero seconds).
    """
    idx = stage_chunk_indices(clients, selected, seed, t0, steps,
                              batch_size)
    on_device = isinstance(store, DeviceStore)
    gather = store.gather if on_device else (lambda i: gather_chunk(store, i))
    if timer is None:
        return gather(idx)
    with timer.phase("stage_gather"):
        out = gather(idx)
    if on_device:
        timer.add("stage_device", 0.0)
    return out


def partition_plan(limited: np.ndarray) -> dict:
    """Host-side dispatch plan for the PARTITIONED client plane.

    ``limited``: (n_rounds, C) bool — the chunk's stacked FES flags from
    ``Environment.batch``. Groups each round's cohorts by limited-ness
    into two programs with STATIC widths across the chunk (the fused
    round scan needs one shape for every round):

      * the limited (classifier-only / truncated) program takes
        ``L = min`` limited count over the chunk's rounds;
      * the full (masked) program takes the remaining ``U = C - L``
        slots — unlimited cohorts plus any round's OVERFLOW limited
        cohorts, which stay correct there (masked, just unreduced).

    A 1-round chunk — the per-round fallback, ``run_round``, the pod
    ``--no-scan`` loop — therefore gets the exact per-round split with
    no overflow. Returned arrays (consumed by
    ``core.client.make_partitioned_local_train`` via the schedule dict):

      part_full_idx (n, U) — cohort slot feeding full-program row u
      part_lim_idx  (n, L) — cohort slot feeding limited-program row l
      part_src_row  (n, C) — slot c's row in its program's stacked output
      part_from_lim (n, C) — True where that program is the limited one
    """
    limited = np.asarray(limited, bool)
    if limited.ndim != 2:
        raise ValueError(f"limited must be (n_rounds, C), got "
                         f"{limited.shape}")
    n, C = limited.shape
    L = int(limited.sum(axis=1).min())
    U = C - L
    full_idx = np.zeros((n, U), np.int32)
    lim_idx = np.zeros((n, L), np.int32)
    src_row = np.zeros((n, C), np.int32)
    from_lim = np.zeros((n, C), bool)
    for i in range(n):
        lim = np.flatnonzero(limited[i])[:L].astype(np.int32)
        full = np.setdiff1d(np.arange(C, dtype=np.int32), lim)
        lim_idx[i], full_idx[i] = lim, full
        from_lim[i, lim] = True
        src_row[i, lim] = np.arange(L, dtype=np.int32)
        src_row[i, full] = np.arange(U, dtype=np.int32)
    return {"part_full_idx": full_idx, "part_lim_idx": lim_idx,
            "part_src_row": src_row, "part_from_lim": from_lim}


class ChunkPrefetcher:
    """Stage chunk k+1 on a host thread while chunk k runs on device.

    ``fn(item)`` is called on a SINGLE worker thread in item order (so
    stateful environments and shared RNG-free staging are safe); at most
    ``depth`` staged chunks are buffered ahead of the consumer.
    """

    def __init__(self, fn, items, depth: int = 1):
        self._q = queue.Queue(maxsize=max(depth, 1))
        self._n = len(items)
        self._stop = threading.Event()

        def put(item) -> bool:
            while not self._stop.is_set():      # closed consumers release
                try:                            # the worker (no leaked
                    self._q.put(item, timeout=0.1)   # thread/chunk buffer)
                    return True
                # fedlint: disable=FED106 — bounded 0.1s poll; _stop is the exit
                except queue.Full:
                    continue
            return False

        def work():
            for it in items:
                if self._stop.is_set():
                    return
                try:
                    staged = (fn(it), None)
                except Exception as e:          # surface on the consumer side
                    put((None, e))
                    return
                if not put(staged):
                    return

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def _drain(self) -> None:
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                return

    def close(self) -> None:
        """Stop staging and drop buffered chunks (abandoned iteration)."""
        self._stop.set()
        self._drain()
        # an in-flight put can land after the first drain; once the
        # worker observes the stop flag and exits, drain what it left
        self._thread.join(timeout=1.0)
        self._drain()

    def __iter__(self):
        try:
            for _ in range(self._n):
                out, err = self._q.get()
                if err is not None:
                    raise err
                yield out
        finally:
            self.close()


def batch_iterator(data: dict, batch_size: int, seed: int = 0):
    n = len(next(iter(data.values())))
    rng = np.random.RandomState(seed)
    while True:
        order = rng.permutation(n)
        for i in range(0, n - batch_size + 1, batch_size):
            sl = order[i:i + batch_size]
            yield {k: v[sl] for k, v in data.items()}
