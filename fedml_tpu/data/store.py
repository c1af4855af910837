"""Host-resident federated dataset with per-round cohort streaming.

The resident ``FederatedArrays`` layout (batching.py) pads EVERY client to
the size of the largest one and keeps the whole dataset in device memory —
elegant at 128 clients, impossible at the reference's client scales
(FederatedEMNIST: 3,400 writers, ``FederatedEMNIST/data_loader.py:15``;
StackOverflow: 342,477 users, ``stackoverflow_nwp/data_loader.py``), and
on power-law partitions (LEAF MNIST, ``MNIST/data_loader.py:87``) one
giant client inflates every client's padded rows.

``FederatedStore`` keeps the dataset as host numpy in CSR form (one flat
sample array sorted by client + offsets) and materializes only the
sampled cohort per round:

  - device memory per round = cohort_size x cohort_max_steps x batch —
    independent of the total client count;
  - the cohort is padded to ITS OWN max count (bucketed to a power of two
    so XLA sees a handful of shapes, not one per round), so power-law
    tails no longer tax every round;
  - ``gather_groups`` goes one step further for the per-round host loop:
    the cohort sorted by step need and cut into ``size_group(k, B)``
    clients a group, each group padded to the bucket of ITS largest
    member, so one giant client pads its own group and no other (the
    size-grouped streamed round, ``FedAvgAPI._train_round_size_grouped``);
  - ``gather_cohort`` returns a regular ``FederatedArrays``, so the
    existing jitted rounds (vmap and shard_map) consume it unchanged;
  - ``CohortPrefetcher`` overlaps the next round's host gather + H2D
    transfer with the current round's compute (double buffering): JAX
    dispatch is async, so ``jnp.asarray`` from the worker thread starts
    the copy immediately;
  - ``gather_window`` stacks W precomputed cohorts into ONE
    ``[W, k, S, B, ...]`` superbatch (a single fancy-index gather into
    reused staging buffers + one H2D transfer per field) for the windowed
    execution tier (``FedAvgAPI.train_rounds_windowed``), with
    ``WindowPrefetcher`` double-buffering the next window's gather + H2D
    against the current window's scan.

Past the flat store's own wall (host RSS is O(dataset)), the
million-client tier shards this layout behind the SAME contract:
``data/directory.py``'s ``ShardedFederatedStore`` overrides only the
``_fill_rows`` storage primitive (per-shard, memmap-backed gathers,
bit-equal — see docs/EXECUTION.md "Scale tiers").
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.data.batching import FederatedArrays, WindowBatch
from fedml_tpu.obs.sanitizer import planned_transfer
from fedml_tpu.obs.trace import span


def _bucket_steps(steps: int) -> int:
    """Round up to a power of two: bounds the number of distinct cohort
    shapes (→ jit retraces) at log2(max_steps)."""
    steps = max(int(steps), 1)
    return 1 << (steps - 1).bit_length()


def bucket_steps_for_counts(counts, batch_size: int) -> np.ndarray:
    """Vectorized :func:`_bucket_steps` of every client's step need —
    the ONE other place the bucket policy is computed (bench warmup must
    warm exactly the shapes the store will produce; a drifted copy would
    let jit recompiles land inside timed windows). Exact bit-twiddle
    round-up, no float log2; pinned equal to the scalar form in
    tests/test_store.py."""
    steps = np.maximum(
        -(-np.asarray(counts, np.int64) // int(batch_size)),
        1).astype(np.uint64)
    v = steps - 1
    for shift in (1, 2, 4, 8, 16, 32):
        v |= v >> np.uint64(shift)
    return (v + 1).astype(np.int64)


#: The size-grouped streamed round (``size_group``): a group's step holds
#: at least this many samples (clients x batch), and a round has between
#: ``MIN_GROUPS`` and ``MAX_GROUPS`` groups. Settled on the chip (PERF.md
#: §6, PR 29; FEMNIST CNN, 200 lognormal clients a round, batch 20, groups
#: of 2 to 50): a sample slot costs less, not more, at 100 samples a step
#: than at 4,000, so narrower groups only gain, until the worker that
#: prepares them (4.5 ms a group: a gather, five puts) falls behind the
#: device. At 20 groups a round it keeps a third of the round in hand; at
#: 40 the round is faster still but waits for the host and spreads 4 % from
#: run to run: raise ``MAX_GROUPS`` when the store's host path is cheaper
#: (ROADMAP S4). Under 8 groups nothing was measured: the whole cohort.
MIN_STEP_SAMPLES = 100
MIN_GROUPS, MAX_GROUPS = 8, 20


def size_group(cohort: int, batch_size: int) -> int:
    """Clients a size group for a sampled cohort of ``cohort`` slots at
    ``batch_size`` samples a step: the smallest divisor of the cohort that
    keeps ``MIN_STEP_SAMPLES`` in a step and the round within ``MAX_GROUPS``
    groups (200 at batch 20 -> 10, 64 -> 8, 1,000 -> 50), so every group has
    the same client count and a round's programs differ in the step bucket
    alone. 0 — the whole cohort as one group, the flat ``gather_cohort``
    round — where no such divisor leaves ``MIN_GROUPS`` groups (a small
    cohort, a small batch, a prime). A rule of the code, not a setting."""
    least = max(-(-MIN_STEP_SAMPLES // int(batch_size)),
                -(-int(cohort) // MAX_GROUPS))
    for g in range(least, int(cohort) // MIN_GROUPS + 1):
        if cohort % g == 0:
            return g
    return 0


class CohortGroup(NamedTuple):
    """One size group of a streamed cohort (``gather_groups``)."""

    fed: FederatedArrays    # the members, [g, steps, B, ...] on the device
    slots: jax.Array        # [g] int32: each member's slot in the cohort
    steps: int              # the group's own power-of-two step bucket


class FederatedStore:
    """CSR host store over a federated dataset.

    ``client_indices`` maps client id (0..C-1) to index arrays into
    ``(x, y)`` — the same contract as ``build_federated_arrays``. The
    store copies samples into client-sorted order once so each client's
    block is one contiguous slice at gather time.
    """

    def __init__(
        self,
        x: np.ndarray,
        y: np.ndarray,
        client_indices: Dict[int, np.ndarray],
        batch_size: int,
        max_steps: Optional[int] = None,
    ):
        n_clients = len(client_indices)
        counts = np.array(
            [len(client_indices[c]) for c in range(n_clients)], np.int64)
        if max_steps is not None:
            counts = np.minimum(counts, max_steps * batch_size)
        order = np.concatenate(
            [np.asarray(client_indices[c])[: counts[c]]
             for c in range(n_clients)]) if counts.sum() else \
            np.zeros((0,), np.int64)
        self._x = np.ascontiguousarray(x[order])
        self._y = np.ascontiguousarray(y[order])
        self._init_meta(counts, batch_size, max_steps,
                        x.shape[1:], x.dtype, y.shape[1:], y.dtype)

    def _init_meta(self, counts, batch_size, max_steps,
                   sample_shape, sample_dtype, label_shape, label_dtype):
        """Shared metadata/staging init — everything about the store that
        is NOT the backing sample storage. ``ShardedFederatedStore``
        (data/directory.py) reuses the whole gather contract through this
        plus the :meth:`_fill_rows` storage primitive."""
        counts = np.asarray(counts, np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(counts)])
        self.counts = counts.astype(np.int32)
        self.batch_size = int(batch_size)
        self.max_steps = max_steps
        self.num_clients = len(counts)
        self._sample_shape = tuple(sample_shape)
        self._sample_dtype = np.dtype(sample_dtype)
        self._label_shape = tuple(label_shape)
        self._label_dtype = np.dtype(label_dtype)
        # Reused host staging buffers for window superbatches (one buffer
        # per (field, shape) — windows of the same span length and bucket
        # refill the same memory instead of re-faulting fresh pages every
        # window). Guarded by a lock: gather_window publishes its device
        # copies BEFORE releasing, so a concurrent gather can never
        # overwrite a buffer an in-flight H2D transfer still reads.
        self._staging: Dict[tuple, np.ndarray] = {}
        self._staging_lock = threading.Lock()

    def example_input(self) -> np.ndarray:
        """One zero batch with the store's sample shape/dtype — what model
        init needs (mirrors ``train_fed.x[0, 0]`` on the resident path)."""
        return np.zeros((self.batch_size,) + self._sample_shape,
                        self._sample_dtype)

    def nbytes(self) -> int:
        return self._x.nbytes + self._y.nbytes

    def cohort_steps(self, indices) -> int:
        """The power-of-two step bucket of a cohort gathered WHOLE — the
        bucket of its largest client, the number ``gather_cohort`` computes
        internally — exposed so window planning
        (``FedAvgAPI.train_rounds_windowed``) can group upcoming rounds by
        bucket WITHOUT gathering them. Not what the size-grouped round
        dispatches: there each group has its own (``plan_groups``)."""
        ccounts = self.counts[np.asarray(indices)]
        return _bucket_steps(
            int(np.ceil(max(int(ccounts.max()), 1) / self.batch_size)))

    def _resolve_steps(self, ccounts: np.ndarray, steps: Optional[int]):
        """Validate/derive the step bucket for a gather over clients with
        per-client counts ``ccounts`` (any shape)."""
        bs = self.batch_size
        need = _bucket_steps(int(np.ceil(max(int(ccounts.max()), 1) / bs)))
        if steps is None:
            return need
        if steps < need:
            raise ValueError(
                f"forced steps {steps} < cohort need {need} "
                f"(max client count {int(ccounts.max())}, batch {bs})")
        return int(steps)

    def _rowmap(self, idx: np.ndarray, cap: int):
        """Precomputed row map for a fancy-index gather: for every cohort
        slot and sample position, the row of the flat CSR arrays to copy.
        Positions past a client's count repeat its FIRST row (the masked
        own-first-sample pad rule of ``build_federated_arrays``). Returns
        ``(rows [*idx.shape, cap] int64, empty [*idx.shape] bool)`` —
        rows of ``empty`` (zero-count) clients point at row 0 and must be
        zeroed after the gather (the loop reference leaves them zero)."""
        lo = self.offsets[idx].astype(np.int64)
        n = (self.offsets[idx + 1] - self.offsets[idx]).astype(np.int64)
        pos = np.arange(cap, dtype=np.int64)
        rows = lo[..., None] + np.where(pos < n[..., None], pos, 0)
        empty = n == 0
        if empty.any():
            rows = np.where(empty[..., None], 0, rows)
        return rows, empty

    def _fill_rows(self, idx: np.ndarray, cap: int,
                   xs: np.ndarray, ys: np.ndarray) -> np.ndarray:
        """The STORAGE PRIMITIVE behind both gathers: fill the
        preallocated ``xs [*idx.shape, cap, ...]`` / ``ys`` with each
        cohort slot's rows (positions past a client's count repeat its
        first row — the masked own-first-sample pad rule) and return the
        ``[*idx.shape]`` bool mask of EMPTY (zero-count) slots, whose
        rows the caller zeroes (this method may leave them unwritten).
        The flat store gathers from its one CSR array pair;
        ``ShardedFederatedStore`` overrides this with per-shard gathers —
        everything above (bucketing, masks, staging, H2D, put contracts)
        is storage-agnostic and shared."""
        rows, empty = self._rowmap(idx, cap)
        np.take(self._x, rows, axis=0, out=xs)
        np.take(self._y, rows, axis=0, out=ys)
        return empty

    def gather_cohort(self, indices,
                      steps: Optional[int] = None) -> FederatedArrays:
        """Materialize the given clients as ONE device-resident
        ``FederatedArrays``, every client padded to the largest of THESE
        clients (power-of-two step bucket): the whole sampled cohort for
        the whole-cohort round, ``pow_d``'s candidate pass and the
        evaluation chunks; one size group at a time for ``gather_groups``.
        Duplicate indices are fine (pad_to_multiple repeats
        index 0 with weight 0). One vectorized fancy-index gather per
        field (byte-identical to :meth:`_gather_cohort_loop`, the scalar
        reference the tests pin it against — the per-client Python copy
        loop cost O(k) interpreter trips per round at reference scale).

        ``steps`` forces the step bucket (must cover the cohort's own
        need): multi-host runs, where each host holds only its
        ``process_local_client_slice`` of the clients, pass the GLOBAL
        cohort bucket (allgather of the per-host maxima) so every host's
        shard of the client-sharded round has identical [S, B] shapes —
        see tests/multihost_worker.py:run_store_rounds."""
        idx = np.asarray(indices)
        k = len(idx)
        ccounts = self.counts[idx]
        steps = self._resolve_steps(ccounts, steps)
        cap = steps * self.batch_size

        with span("fed.store.gather", clients=k, steps=steps):
            xs = np.empty((k, cap) + self._sample_shape, self._sample_dtype)
            ys = np.empty((k, cap) + self._label_shape, self._label_dtype)
            empty = self._fill_rows(idx, cap, xs, ys)
            mask = (np.arange(cap) < ccounts[:, None]).astype(np.float32)
            if empty.any():
                xs[empty] = 0
                ys[empty] = 0
            counts = ccounts.astype(np.int32)

        def split(a):
            return a.reshape((k, steps, self.batch_size) + a.shape[2:])

        # planned_transfer: the cohort H2D is the streaming tier's ONE
        # deliberate staging copy per round — mark it so the whole round
        # loop can run under obs.sanitizer.sanitized()'s transfer guard.
        nbytes = xs.nbytes + ys.nbytes + mask.nbytes + counts.nbytes
        with span("fed.store.put", bytes=nbytes), planned_transfer():
            return FederatedArrays(
                x=jnp.asarray(split(xs)),
                y=jnp.asarray(split(ys)),
                mask=jnp.asarray(split(mask)),
                counts=jnp.asarray(counts),
            )

    def plan_groups(self, indices,
                    group: int) -> List[Tuple[np.ndarray, int]]:
        """The size groups of a sampled cohort, from the counts alone:
        slots ordered by step need (``ceil(count / batch)``; stable, so
        ties keep cohort order) and cut into ``group`` clients a group,
        each with the power-of-two bucket of ITS largest member. Returns
        ``[(slots [group] int32, steps)]``, smallest needs first; the
        buckets are among ``bucket_steps_for_counts(self.counts)``
        whatever the round draws."""
        idx = np.asarray(indices)
        if group < 1 or len(idx) % group:
            raise ValueError(
                f"groups of {group} do not divide a cohort of {len(idx)}")
        need = np.maximum(
            -(-self.counts[idx].astype(np.int64) // self.batch_size), 1)
        order = np.argsort(need, kind="stable").astype(np.int32)
        # ascending: a group's largest member is its last
        return [(order[lo:lo + group],
                 _bucket_steps(int(need[order[lo + group - 1]])))
                for lo in range(0, len(idx), group)]

    def gather_groups(self, indices, group: int) -> List[CohortGroup]:
        """The sampled cohort as its size groups (``plan_groups``), each
        ``gather_cohort(indices[slots], steps)`` — the same fill, mask, put
        and spans at the group's ``[group, steps, B, ...]`` shape — so the
        round trains no client at a larger bucket than its group's."""
        idx = np.asarray(indices)
        out = []
        for slots, steps in self.plan_groups(idx, group):
            fed = self.gather_cohort(idx[slots], steps=steps)
            with planned_transfer():
                out.append(CohortGroup(fed, jnp.asarray(slots), steps))
        return out

    def _gather_cohort_loop(self, indices,
                            steps: Optional[int] = None) -> FederatedArrays:
        """The original per-client copy-loop gather, kept as the scalar
        REFERENCE implementation: tests assert ``gather_cohort``'s
        vectorized fancy-index path stays byte-identical to it. Not used
        on any hot path."""
        idx = np.asarray(indices)
        k = len(idx)
        ccounts = self.counts[idx]
        steps = self._resolve_steps(ccounts, steps)
        cap = steps * self.batch_size

        xs = np.zeros((k, cap) + self._x.shape[1:], self._x.dtype)
        ys = np.zeros((k, cap) + self._y.shape[1:], self._y.dtype)
        mask = np.zeros((k, cap), np.float32)
        for j, c in enumerate(idx):
            lo, hi = int(self.offsets[c]), int(self.offsets[c + 1])
            n = hi - lo
            if n == 0:
                continue
            xs[j, :n] = self._x[lo:hi]
            ys[j, :n] = self._y[lo:hi]
            mask[j, :n] = 1.0
            if n < cap:  # pad with the client's own first sample (masked)
                xs[j, n:] = self._x[lo]
                ys[j, n:] = self._y[lo]

        def split(a):
            return a.reshape((k, steps, self.batch_size) + a.shape[2:])

        return FederatedArrays(
            x=jnp.asarray(split(xs)),
            y=jnp.asarray(split(ys)),
            mask=jnp.asarray(split(mask)),
            counts=jnp.asarray(ccounts, jnp.int32),
        )

    def window_weights(self, window_indices, wmask) -> np.ndarray:
        """``[W, k]`` float32 aggregation weights for a window: per-slot
        sample counts gathered through the window's index map, zeroed at
        padded slots (``wmask``). The window-keyed companion of
        :meth:`gather_window` for count-derived per-round state — host
        math (one fancy-index gather over ``counts``), shared by the
        windowed executor's weights and the carry protocol's masks so
        they can never drift from the per-round host loop's
        ``sub.counts * wmask``."""
        idx = np.asarray(window_indices)
        return (self.counts[idx].astype(np.float32)
                * np.asarray(wmask, np.float32))

    def window_trained_mask(self, window_indices, wmask) -> np.ndarray:
        """``[W, k]`` float32 mask of slots that actually TRAIN in their
        round: active (un-padded) AND non-empty. Algorithms that carry
        per-client state through the window scan (SCAFFOLD's controls)
        gate their scatter-back on this — a sampled EMPTY client runs
        zero real steps and must not write its state slot (same rule as
        the per-round host loop)."""
        idx = np.asarray(window_indices)
        return (np.asarray(wmask, np.float32)
                * (self.counts[idx] > 0).astype(np.float32))

    def _staged(self, field: str, shape: tuple, dtype) -> np.ndarray:
        """Reused staging buffer, one per (field, shape, dtype) — keyed
        by the full shape so alternating window-max buckets (giant
        client in/out of the window) each keep their own buffer instead
        of thrashing a single slot with reallocations. Shape count is
        bounded by the power-of-two bucket count. Caller must hold
        ``_staging_lock``."""
        key = (field, shape, np.dtype(dtype).str)
        buf = self._staging.get(key)
        if buf is None:
            buf = np.empty(shape, dtype)
            self._staging[key] = buf
        return buf

    def gather_window(self, window_indices, steps: int,
                      put=None) -> WindowBatch:
        """Gather W rounds' cohorts into ONE ``[W, k, S, B, ...]``
        superbatch: a single fancy-index gather per field (precomputed row
        maps, reused staging buffers) and a single H2D transfer per field,
        instead of W per-round gather + transfer round-trips.

        ``window_indices`` is the ``[W, k]`` array of per-round padded
        cohort indices (known in advance under seeded-random selection).
        ``steps`` is the window's SHARED step bucket and must cover every
        round's own need; the windowed executor passes the window-max
        bucket, so a round whose natural bucket is smaller gets extra
        masked pad rows — its slice equals its own
        ``gather_cohort(idx, steps=steps)`` with the same forced bucket
        (tested), and training on it is an exact no-op relative to the
        natural bucket because the trainer's rng streams are
        prefix-stable in the step count (``trainer.local``).

        ``put`` maps each staged host array to the device (default
        ``jnp.array`` — an EXPLICIT copy: the CPU backend may otherwise
        alias numpy memory zero-copy, and the staging buffers are
        refilled next window); mesh runs pass a sharded ``device_put``.
        A custom ``put`` must either copy before putting and declare it
        (``put.copies = True``, as ``parallel.shard.window_put`` does)
        or accept the defensive ``np.array`` copy this method inserts —
        the PR-1 aliasing bug class (fedlint R2) is a put that zero-copy
        aliases a staging buffer the next window refills.
        The device arrays are blocked on before the staging lock is
        released, so buffer reuse can never race an in-flight transfer."""
        idx = np.asarray(window_indices)
        if idx.ndim != 2:
            raise ValueError(f"window_indices must be [W, k], got {idx.shape}")
        w, k = idx.shape
        ccounts = self.counts[idx]
        steps = self._resolve_steps(ccounts, steps)
        cap = steps * self.batch_size
        if put is None:
            put, put_copies = jnp.array, True  # jnp.array copies by default
        else:
            put_copies = bool(getattr(put, "copies", False))

        with self._staging_lock:
            xs = self._staged("x", (w, k, cap) + self._sample_shape,
                              self._sample_dtype)
            ys = self._staged("y", (w, k, cap) + self._label_shape,
                              self._label_dtype)
            empty = self._fill_rows(idx, cap, xs, ys)
            if empty.any():
                xs[empty] = 0
                ys[empty] = 0
            mask = (np.arange(cap) < ccounts[..., None]).astype(np.float32)

            def split(a):
                return a.reshape((w, k, steps, self.batch_size) + a.shape[3:])

            def staged_put(a):
                # R2 staging-alias guard: a put that has not declared
                # ``copies = True`` may alias the reused staging buffer
                # zero-copy (jax.device_put does, on the CPU backend) —
                # hand it a fresh copy, the same guard window_put carries
                # internally. ``mask`` is freshly allocated per call, so
                # only the staged x/y fields need it.
                return put(a if put_copies else np.array(a))

            # planned_transfer: the window superbatch H2D is THE
            # deliberate staging copy of the windowed tier (one per
            # window) — mark it for obs.sanitizer.sanitized() regions.
            with planned_transfer():
                batch = WindowBatch(
                    x=staged_put(split(xs)),
                    y=staged_put(split(ys)),
                    mask=put(split(mask)),
                    counts=jnp.asarray(ccounts, jnp.int32),
                )
                # Block INSIDE the lock: once we release, the next window
                # may refill xs/ys while these transfers still read them.
                jax.block_until_ready((batch.x, batch.y, batch.mask))
        return batch


class CohortPrefetcher:
    """Double buffer: prepare round r+1's cohort (host gather + async H2D)
    on a worker thread while round r computes. ``get`` blocks on the
    in-flight preparation only if it has not finished yet. ``group`` > 0
    prepares and hands over the cohort as its size groups
    (``gather_groups``) instead of one ``FederatedArrays``."""

    def __init__(self, store: FederatedStore):
        self.store = store
        self._pending: Dict[int, threading.Thread] = {}
        # round -> (indices, group, cohort)
        self._ready: Dict[int, tuple] = {}
        self._lock = threading.Lock()

    def _gather(self, indices, group: int):
        if group:
            return self.store.gather_groups(indices, group)
        return self.store.gather_cohort(indices)

    def prefetch(self, round_idx: int, indices, group: int = 0) -> None:
        indices = np.asarray(indices)

        def work():
            try:
                with span("fed.cohort.prefetch", round=round_idx):
                    cohort = self._gather(indices, group)
                with self._lock:
                    self._ready[round_idx] = (indices, group, cohort)
            finally:
                # Always clear pending — a worker failure (host OOM, bad
                # index) must not permanently block future prefetches for
                # this round; get() then re-gathers synchronously and the
                # real exception surfaces in the caller's context.
                with self._lock:
                    self._pending.pop(round_idx, None)

        t = threading.Thread(target=work, name="fed-cohort-prefetch",
                             daemon=True)
        with self._lock:
            # Membership check and registration under ONE acquisition:
            # check-then-act across two lock scopes would let concurrent
            # prefetch calls for the same round both spawn gather threads.
            if round_idx in self._pending or round_idx in self._ready:
                return
            self._pending[round_idx] = t
        t.start()

    def get(self, round_idx: int, indices, group: int = 0):
        # On a miss the synchronous gather's fed.store.* spans lie inside
        # this one, on the caller's thread: that nesting is the miss signal.
        with span("fed.cohort.wait", round=round_idx):
            with self._lock:
                t = self._pending.get(round_idx)
            if t is not None:
                t.join()
            with self._lock:
                hit = self._ready.pop(round_idx, None)
                # Drop stale buffers (a user skipping rounds must not leak).
                for r in [r for r in self._ready if r < round_idx]:
                    self._ready.pop(r)
            # The prefetched cohort is only valid for the EXACT index list
            # and form the caller now wants — sampling inputs may have
            # changed between the prefetch and the round (cfg mutation,
            # subclass overrides).
            if hit is not None and hit[1] == group and np.array_equal(
                    hit[0], np.asarray(indices)):
                return hit[2]
            return self._gather(indices, group)


class WindowPrefetcher:
    """Double buffer for window superbatches: gather + H2D of window w+1
    on a worker thread while window w's scan computes. A worker failure
    (host OOM, bad index) is CONTAINED: the exception is captured and
    re-raised in the caller's ``get`` — never a deadlock, never a
    silently-dropped window — and the prefetcher stays usable afterwards
    (subsequent gets fall through to a synchronous gather)."""

    def __init__(self, store: FederatedStore, put=None):
        self.store = store
        self.put = put
        self._pending: Dict[int, threading.Thread] = {}
        # key -> ("ok", (indices, steps, batch)) | ("err", exception)
        self._done: Dict[int, tuple] = {}
        self._lock = threading.Lock()

    def prefetch(self, key: int, window_indices, steps: int) -> None:
        indices = np.asarray(window_indices)

        def work():
            try:
                res = ("ok", (indices, steps,
                              self.store.gather_window(
                                  indices, steps, put=self.put)))
            except BaseException as e:  # surfaces in get(), not the log
                res = ("err", e)
            with self._lock:
                self._done[key] = res
                self._pending.pop(key, None)

        t = threading.Thread(target=work, daemon=True)
        with self._lock:
            if key in self._pending or key in self._done:
                return
            self._pending[key] = t
        t.start()

    def get(self, key: int, window_indices, steps: int) -> WindowBatch:
        with self._lock:
            t = self._pending.get(key)
        if t is not None:
            t.join()
        with self._lock:
            hit = self._done.pop(key, None)
            for stale in [s for s in self._done if s < key]:
                self._done.pop(stale)  # skipped windows must not leak
        if hit is not None:
            tag, val = hit
            if tag == "err":
                raise val
            pidx, psteps, batch = val
            if psteps == steps and np.array_equal(
                    pidx, np.asarray(window_indices)):
                return batch
        return self.store.gather_window(window_indices, steps, put=self.put)
