"""Distributed data pipeline.

Capability parity: reference `src/accelerate/data_loader.py` (1321 LoC) —
`BatchSamplerShard`, `IterableDatasetShard`, `SeedableRandomSampler`,
`DataLoaderShard`, `DataLoaderDispatcher`, `prepare_data_loader`,
`skip_first_batches` (reference lines :103, :259, :68, :486, :680, :930, :1245).

TPU-native re-founding:
  - A "process" is a host; each host loads only its slice of the global batch and
    the loader assembles a single *global* `jax.Array` per leaf, sharded over the
    mesh's data axes (`jax.make_array_from_process_local_data`). Downstream, the
    jitted step consumes global arrays — there is no per-rank tensor plumbing.
  - XLA requires static shapes, so ragged final batches are padded *by wrapping
    samples from the batch start* (the reference's `even_batches` semantics) and
    the duplicate count is recorded in `remainder` for `gather_for_metrics` to
    drop (reference `accelerator.py:2487-2505`).
  - Host->device transfer is asynchronous in JAX; a one-batch lookahead both
    overlaps the copy and detects `end_of_dataloader` for gradient-sync
    bookkeeping (reference `data_loader.py:550-573`), replacing torch_xla's
    `MpDeviceLoader` background threads.

Works with torch `DataLoader`s (rebuilt around a sharded batch sampler, keeping
collate/workers) or with any python iterable yielding numpy/dict batches.
"""

from __future__ import annotations

import logging
import math
from collections.abc import Mapping
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .state import AcceleratorState, GradientState, PartialState
from .parallel.mesh import data_axes
from .utils.operations import (
    as_registered_pytree,
    broadcast_object_list,
    find_batch_size,
    recursively_apply,
)
from .utils import spans
from .utils.random import get_rng_key, synchronize_rng_states

logger = logging.getLogger(__name__)


def _leaf_to_numpy(t: Any) -> Any:
    """Convert a torch tensor / jax array leaf to numpy, pass others through."""
    if isinstance(t, np.ndarray):
        return t
    if isinstance(t, jax.Array):
        return np.asarray(t)
    # torch tensors, without importing torch eagerly
    if type(t).__module__.startswith("torch") and hasattr(t, "detach"):
        return t.detach().cpu().numpy()
    return t


def _is_arraylike(t: Any) -> bool:
    return (
        isinstance(t, (np.ndarray, jax.Array))
        or (type(t).__module__.startswith("torch") and hasattr(t, "detach"))
    )


class SeedableRandomSampler:
    """Deterministic, resumable shuffling sampler re-seeded per epoch
    (reference `data_loader.py:68-100`). Framework-agnostic: yields indices."""

    def __init__(self, data_source_len: int, seed: int = 0, epoch: int = 0):
        self.data_source_len = data_source_len
        self.seed = seed
        self.epoch = epoch

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __iter__(self) -> Iterator[int]:
        rng = np.random.default_rng(self.seed + self.epoch)
        yield from rng.permutation(self.data_source_len).tolist()
        self.epoch += 1

    def __len__(self) -> int:
        return self.data_source_len


class BatchSamplerShard:
    """Yield this process's share of a batch sampler's batches
    (reference `data_loader.py:103-257`). Two modes:

    - ``split_batches=True``: every underlying batch (the *global* batch) is cut
      into ``num_processes`` contiguous slices; this shard yields slice
      ``process_index``. The underlying batch size must divide evenly.
    - ``split_batches=False``: whole batches go round-robin; this shard takes
      batches ``process_index, process_index+P, ...``.

    With ``even_batches=True`` (default), sample indices wrap around to the
    dataset start so every process yields the same number of equally-sized
    batches — the static-shape guarantee the jitted step requires. With
    ``even_batches=False`` trailing batches may be smaller or missing.
    """

    def __init__(
        self,
        batch_sampler: Iterable[list[int]],
        num_processes: int,
        process_index: int,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        if not 0 <= process_index < num_processes:
            raise ValueError(f"process_index {process_index} out of range for {num_processes} processes")
        self.batch_sampler = batch_sampler
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        if self.split_batches and self.batch_size is not None and self.batch_size % num_processes != 0:
            raise ValueError(
                f"split_batches requires batch size ({self.batch_size}) divisible by "
                f"num_processes ({num_processes})"
            )
        if self.batch_size is None and even_batches:
            # evening pads to the NOMINAL batch size; without one the pad target
            # is undefined (reference `data_loader.py:158-162` same rule)
            raise ValueError(
                "even_batches=True requires the batch sampler to expose `batch_size`; "
                "pass even_batches=False for samplers with variable batch sizes"
            )
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    @property
    def total_length(self) -> int:
        return len(self.batch_sampler)

    def __len__(self) -> int:
        n = len(self.batch_sampler)
        if self.split_batches:
            return n
        if self.drop_last:
            # a trailing group with fewer than num_processes batches is dropped
            # entirely (reference `data_loader.py:199-205` length math)
            return n // self.num_processes
        if self.even_batches:
            return math.ceil(n / self.num_processes)
        # without evening, later processes may get one fewer batch
        base, extra = divmod(n, self.num_processes)
        return base + (1 if self.process_index < extra else 0)

    def __iter__(self) -> Iterator[list[int]]:
        if self.split_batches:
            yield from self._iter_split()
        else:
            yield from self._iter_round_robin()

    def _iter_split(self) -> Iterator[list[int]]:
        """Slice math is anchored on the NOMINAL batch size (reference
        `data_loader.py:189-209`): a ragged batch — including a first batch
        smaller than one global batch — is refilled by cycling the epoch's
        first batch, so every yielded slice has the static nominal/P shape."""
        nominal = self.batch_size
        if nominal is None:
            # no declared batch size (ctor forces even_batches=False): pure
            # exact partition — each batch sliced by its own ceil(len/P),
            # empty pieces skipped (reference batch_size-None role)
            for batch in self.batch_sampler:
                batch = list(batch)
                size = math.ceil(len(batch) / self.num_processes)
                piece = batch[size * self.process_index : size * (self.process_index + 1)]
                if piece:
                    yield piece
            return
        size = nominal // self.num_processes
        first: list[int] | None = None
        last: list[int] = []
        for batch in self.batch_sampler:
            batch = list(batch)
            if first is None:
                first = batch
            if last and len(last) != nominal:
                # the slice math assumes only the FINAL batch may be ragged
                # (torch BatchSampler invariant; the reference silently DROPS
                # mid-stream ragged batches — raise instead of losing samples)
                raise ValueError(
                    f"batch of {len(last)} followed by more batches; only the final "
                    f"batch may differ from the nominal size {nominal}"
                )
            last = batch
            if len(batch) == nominal:
                yield batch[size * self.process_index : size * (self.process_index + 1)]
        if first is None or len(last) == nominal or self.drop_last:
            return  # empty sampler, or no ragged tail, or tail dropped
        if not self.even_batches:
            piece = last[size * self.process_index : size * (self.process_index + 1)]
            if piece:
                yield piece
            return
        pool = list(first)
        while len(pool) < nominal:  # dataset smaller than one global batch
            pool = pool + pool
        refill = (last + pool)[:nominal]
        yield refill[size * self.process_index : size * (self.process_index + 1)]

    def _iter_round_robin(self) -> Iterator[list[int]]:
        """Whole batches go round-robin; a trailing group short of
        ``num_processes`` full batches is completed by wrapping already-seen
        indices (even_batches) or dropped whole (drop_last) — reference
        `data_loader.py:211-257` group semantics, static nominal shapes."""
        nominal = self.batch_size
        group: list[list[int]] = []
        seen: list[int] = []
        ragged_seen = False
        for batch in self.batch_sampler:
            batch = list(batch)
            if nominal is not None:
                if ragged_seen:
                    # padding math assumes only the FINAL batch may be ragged
                    # (torch BatchSampler invariant; the reference silently
                    # loses trailing batches here — raise instead)
                    raise ValueError(
                        "a ragged batch was followed by more batches; only the "
                        f"final batch may differ from the nominal size {nominal}"
                    )
                ragged_seen = len(batch) != nominal
            seen.extend(batch)
            group.append(batch)
            # without a declared batch size (even_batches=False ctor-enforced)
            # every complete group yields regardless of batch sizes
            if len(group) == self.num_processes and (
                nominal is None or len(group[-1]) == nominal
            ):
                yield group[self.process_index]
                group = []
        # trailing group: fewer than num_processes batches, or ragged last batch
        if not group:
            return
        if self.drop_last:
            # dropped whole, never wrapped — torch DataLoader drop_last
            # semantics extend to the process group
            return
        if not self.even_batches:
            if self.process_index < len(group):
                yield group[self.process_index]
            return
        # complete the group to num_processes full batches by cycling seen
        # indices; each process's refill continues where the previous stopped
        k = 0
        filled: list[list[int]] = []
        for i in range(self.num_processes):
            b = list(group[i]) if i < len(group) else []
            while len(b) < nominal:
                b.append(seen[k % len(seen)])
                k += 1
            filled.append(b)
        yield filled[self.process_index]


class IterableDatasetShard:
    """Shard an iterable (length-unknown) dataset across processes by buffering
    ``global_batch`` items and yielding this process's contiguous slice
    (reference `data_loader.py:259-356`). The final short buffer is completed by
    wrapping items from the first buffer unless ``drop_last``.
    """

    def __init__(
        self,
        dataset: Iterable,
        batch_size: int,
        num_processes: int,
        process_index: int,
        drop_last: bool = False,
        split_batches: bool = False,
        seed: int = 0,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.num_processes = num_processes
        self.process_index = process_index
        self.drop_last = drop_last
        self.split_batches = split_batches
        self.seed = seed
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __iter__(self):
        # chunk = one global batch worth of items
        per_proc = self.batch_size // self.num_processes if self.split_batches else self.batch_size
        chunk_size = per_proc * self.num_processes
        first_chunk: list | None = None
        buffer: list = []
        for item in self.dataset:
            buffer.append(item)
            if len(buffer) == chunk_size:
                if first_chunk is None:
                    first_chunk = list(buffer)
                start = per_proc * self.process_index
                yield from buffer[start : start + per_proc]
                buffer = []
        if not buffer or self.drop_last:
            return
        if first_chunk is None:
            first_chunk = list(buffer)
        while len(buffer) < chunk_size:
            buffer.append(first_chunk[(len(buffer)) % len(first_chunk)])
        start = per_proc * self.process_index
        yield from buffer[start : start + per_proc]


class _PrefetchIterator:
    """One-batch lookahead so the consumer learns `end_of_dataloader` before the
    final step and H2D transfer overlaps compute (reference `data_loader.py:550-573`)."""

    def __init__(self, iterator: Iterator, on_last: Callable[[], None]):
        self._it = iterator
        self._on_last = on_last
        self._lookahead = None
        self._primed = False

    @property
    def in_flight(self) -> int:
        """Batches pulled from the underlying iterator but not yet yielded —
        checkpoint state surgery subtracts these (reference
        `data_loader.py:449` adjust_state_dict_for_prefetch)."""
        return 1 if self._lookahead is not None else 0

    def __iter__(self):
        return self

    def __next__(self):
        if not self._primed:
            self._lookahead = next(self._it)  # StopIteration propagates for empty loaders
            self._primed = True
        current = self._lookahead
        try:
            self._lookahead = next(self._it)
        except StopIteration:
            self._on_last()
            self._lookahead = None
            self._it = iter(())
            if current is None:
                raise
        if current is None:
            raise StopIteration
        return current


# counter keys a stateful loader snapshot uses for "already consumed", by UNIT:
# batch-unit keys (torchdata StatefulDataLoader's snapshot tree plus our own
# test fixtures) rewind by the in-flight batch count; sample-unit keys
# (sampler positions) rewind by in_flight × batch_size. Mixing the units would
# desync the sampler from the fetcher on resume.
_PREFETCH_BATCH_KEYS = frozenset(
    {"_snapshot_step", "_num_yielded", "_sampler_iter_yielded",
     "_num_batches_fetched", "num_batches_yielded"}
)
_PREFETCH_SAMPLE_KEYS = frozenset({"samples_yielded"})


def adjust_state_dict_for_prefetch(
    snapshot: Any, in_flight: int, batch_size: int | None = None
) -> Any:
    """Rewind every consumed-counter in a stateful loader's snapshot by the
    number of batches the prefetch chain has pulled ahead of the training step
    (reference `data_loader.py:449` ``adjust_state_dict_for_prefetch``). The
    walk is structural: nested mapping keys in the batch-unit set are
    decremented by ``in_flight``, sample-unit keys by
    ``in_flight * batch_size``, all clamped at 0, rest verbatim. When
    ``batch_size`` is unknown, sample-unit keys are left untouched and a
    warning explains the possible sampler desync."""
    sample_rewind = in_flight * batch_size if batch_size else None

    def _walk(node: Any) -> Any:
        if isinstance(node, Mapping):
            items = {}
            for k, v in node.items():
                if k in _PREFETCH_BATCH_KEYS and isinstance(v, int):
                    items[k] = max(v - in_flight, 0)
                elif k in _PREFETCH_SAMPLE_KEYS and isinstance(v, int):
                    if sample_rewind is None:
                        import warnings

                        warnings.warn(
                            f"stateful loader snapshot has sample-unit counter {k!r} "
                            "but the base loader exposes no batch_size; leaving it "
                            "unadjusted may desync the sampler by up to "
                            f"{in_flight} prefetched batch(es) on resume."
                        )
                        items[k] = v
                    else:
                        items[k] = max(v - sample_rewind, 0)
                else:
                    items[k] = _walk(v)
            try:
                return type(node)(items)
            except TypeError:  # Mapping subtypes w/o dict ctor (defaultdict, ...)
                return items
        if isinstance(node, (list, tuple)):
            walked = [_walk(v) for v in node]
            if hasattr(node, "_fields"):  # namedtuple: positional ctor
                return type(node)(*walked)
            return type(node)(walked)
        return node

    return _walk(snapshot)


class DataLoaderShard:
    """Per-process loader wrapper that yields *global, mesh-sharded* batches.

    Reference `data_loader.py:486-624` (+ the XLA `MpDeviceLoaderWrapper` role,
    `:627-677`, which JAX's async dispatch subsumes).
    """

    def __init__(
        self,
        base_loader: Iterable,
        device_placement: bool = True,
        mesh=None,
        rng_types: list[str] | None = None,
        synchronized_generator: SeedableRandomSampler | None = None,
        skip_batches: int = 0,
        total_dataset_length: int | None = None,
        total_batch_size: int | None = None,
        even_batches: bool = True,
        _drop_last: bool = False,
        prefetch: str = "none",
        prefetch_slot_bytes: int = 256 << 20,
    ):
        if prefetch not in ("none", "auto", "native"):
            raise ValueError(f"prefetch must be none|auto|native, got {prefetch!r}")
        self.prefetch = prefetch
        self.prefetch_slot_bytes = prefetch_slot_bytes
        self.base_loader = base_loader
        self.device_placement = device_placement
        self.mesh = mesh
        self.rng_types = rng_types
        self.synchronized_generator = synchronized_generator
        self.skip_batches = skip_batches
        self.total_dataset_length = total_dataset_length
        self._total_batch_size = total_batch_size
        self.even_batches = even_batches
        self._drop_last = _drop_last
        self.end_of_dataloader = False
        self.remainder = -1
        self.iteration = 0
        self.batches_seen_in_epoch = 0
        self.gradient_state = GradientState()
        if total_dataset_length is not None and total_batch_size:
            if not _drop_last and total_dataset_length % total_batch_size != 0:
                self.remainder = total_dataset_length % total_batch_size

    # ----------------------------------------------------------- properties
    @property
    def total_batch_size(self) -> int | None:
        return self._total_batch_size

    @property
    def dataset(self):
        return getattr(self.base_loader, "dataset", None)

    @property
    def batch_sampler(self):
        return getattr(self.base_loader, "batch_sampler", None)

    def set_epoch(self, epoch: int) -> None:
        self.iteration = epoch
        for obj in (self.batch_sampler, getattr(self.batch_sampler, "batch_sampler", None),
                    self.synchronized_generator, self.dataset):
            if obj is not None and hasattr(obj, "set_epoch"):
                obj.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.base_loader)

    # ------------------------------------------------------------- iteration
    def _data_sharding(self) -> NamedSharding:
        mesh = self.mesh if self.mesh is not None else AcceleratorState().mesh
        return NamedSharding(mesh, PartitionSpec(data_axes(mesh)))

    def _to_global(self, batch: Any) -> Any:
        """numpy/torch leaves -> one global jax.Array per leaf, sharded on the
        data axes. Pads a ragged leading dim by wrapping (static shapes for XLA).
        On the device-placement path, unregistered Mapping containers (HF
        BatchEncoding/UserDict) are normalized to plain dicts so the batch can
        cross the jit boundary; the host-only path keeps the user's container."""
        if not self.device_placement:
            return recursively_apply(_leaf_to_numpy, batch, test_type=_is_arraylike)
        batch = as_registered_pytree(batch)
        sharding = self._data_sharding()
        mesh = sharding.mesh
        shards = math.prod(mesh.shape[a] for a in data_axes(mesh))
        num_processes = PartialState().num_processes
        per_process_shards = max(shards // num_processes, 1)

        # A ragged final batch on ONE process is the whole global batch: record
        # how many samples are real so gather_for_metrics can drop the wrap
        # padding (sized datasets precompute this in __init__; iterables can't).
        if num_processes == 1 and self.end_of_dataloader and self.remainder < 0:
            bs = find_batch_size(batch)
            if bs is not None and bs % per_process_shards != 0:
                self.remainder = bs

        def _place(t):
            t = _leaf_to_numpy(t)
            if t.ndim >= 1 and t.shape[0] % per_process_shards != 0:
                target = math.ceil(t.shape[0] / per_process_shards) * per_process_shards
                reps = [t[i % t.shape[0]] for i in range(t.shape[0], target)]
                t = np.concatenate([t, np.stack(reps)], axis=0)
            if num_processes == 1:
                return jax.device_put(t, sharding)
            return jax.make_array_from_process_local_data(sharding, t)

        return recursively_apply(_place, batch, test_type=_is_arraylike)

    def __iter__(self):
        if self.rng_types is not None:
            synchronize_rng_states(self.rng_types)
        self.gradient_state._add_dataloader(self)
        self.end_of_dataloader = False
        self.batches_seen_in_epoch = 0
        try:
            def _mark_last():
                self.end_of_dataloader = True

            base_it = iter(self.base_loader)
            if self.prefetch in ("auto", "native"):
                # C++ staging ring: host batch assembly + aligned gather-copy of
                # batch i+1 overlap device compute on batch i (native/).
                # Wrapped INSIDE the lookahead iterator so end_of_dataloader
                # still flips exactly when the final batch is yielded.
                from .native import HostPrefetcher, is_native_available, native_unavailable_reason

                if is_native_available():
                    self._live_host_prefetcher = HostPrefetcher(
                        base_it, slot_bytes=self.prefetch_slot_bytes
                    )
                    base_it = iter(self._live_host_prefetcher)
                    logger.info("prefetch=%r: native C++ staging ring", self.prefetch)
                elif self.prefetch == "native":
                    raise RuntimeError(
                        f"prefetch='native' requested but {native_unavailable_reason()}"
                    )
                else:
                    # which path fed the step must be readable from the log:
                    # the library builds on first use and a tree without a
                    # toolchain runs the Python path
                    logger.info("prefetch='auto': Python path (%s)",
                                native_unavailable_reason())
            it = _PrefetchIterator(base_it, _mark_last)
            self._live_prefetch_it = it
            feed = enumerate(it)
            while True:
                # what one `__next__` costs the step loop: producing the host
                # batch (skipped ones included) and placing it on the mesh
                with spans.span("train.input_wait") as wait:
                    for idx, batch in feed:
                        if idx >= self.skip_batches:
                            break
                    else:
                        wait.drop()  # exhausted: no batch was waited for
                        return
                    wait.attrs["batch"] = idx  # its place in the epoch
                    self.batches_seen_in_epoch = idx + 1
                    placed = self._to_global(batch)
                yield placed
        finally:
            self.gradient_state._remove_dataloader(self)
            self.skip_batches = 0
            self._live_prefetch_it = None
            self._live_host_prefetcher = None

    def _in_flight_batches(self) -> int:
        """Batches the prefetch chain has consumed from ``base_loader`` beyond
        what this loader has yielded: the one-batch lookahead plus whatever the
        native staging ring holds."""
        n = 0
        if getattr(self, "_live_prefetch_it", None) is not None:
            n += self._live_prefetch_it.in_flight
        if getattr(self, "_live_host_prefetcher", None) is not None:
            n += self._live_host_prefetcher.in_flight
        return n

    # ----------------------------------------------------- checkpoint support
    def state_dict(self) -> dict[str, Any]:
        """Mid-epoch resumable state (reference StatefulDataLoader adapter,
        `data_loader.py:401-483`). When the wrapped loader is itself stateful
        (torchdata StatefulDataLoader), its snapshot — including worker /
        prefetched-batch state — is carried verbatim; the synchronized
        sampler's RNG state rides along so shuffling resumes identically."""
        state = {
            "iteration": self.iteration,
            "batches_seen_in_epoch": self.batches_seen_in_epoch,
            "end_of_dataloader": self.end_of_dataloader,
        }
        if hasattr(self.base_loader, "state_dict"):
            try:
                snapshot = self.base_loader.state_dict()
            except Exception:
                snapshot = None  # loader advertises state but can't produce it
            if snapshot is not None:
                # adjustment errors must propagate: swallowing them here would
                # silently drop the whole snapshot and restart the dataset
                in_flight = self._in_flight_batches()
                if in_flight:
                    snapshot = adjust_state_dict_for_prefetch(
                        snapshot, in_flight,
                        batch_size=getattr(self.base_loader, "batch_size", None),
                    )
                state["base_loader"] = snapshot
        sampler = self.synchronized_generator
        if sampler is not None and hasattr(sampler, "epoch"):
            state["sampler_epoch"] = sampler.epoch
        return state

    def load_state_dict(self, state: dict[str, Any]) -> None:
        self.iteration = state["iteration"]
        self.set_epoch(self.iteration)
        if "base_loader" in state and hasattr(self.base_loader, "load_state_dict"):
            try:
                self.base_loader.load_state_dict(state["base_loader"])
                return  # the base loader resumes mid-epoch itself: no re-skip
            except Exception:
                pass
        if "sampler_epoch" in state and self.synchronized_generator is not None:
            if hasattr(self.synchronized_generator, "set_epoch"):
                self.synchronized_generator.set_epoch(state["sampler_epoch"])
        if not state.get("end_of_dataloader", False):
            self.skip_batches = state.get("batches_seen_in_epoch", 0)


class DataLoaderDispatcher(DataLoaderShard):
    """Process-0-reads-everything mode (default for iterable datasets in the
    reference — `data_loader.py:680-908`): the main process fetches the full
    global batch and broadcasts it; every process slices its shard and the global
    array is assembled exactly as in `DataLoaderShard`.

    On TPU pods this trades DCN broadcast bandwidth for not needing a splittable
    dataset on every host — same trade the reference makes over NCCL.

    Ragged final batch: the reference completes it from ``first_batch`` under
    ``even_batches`` and yields uneven slices otherwise
    (`data_loader.py:812-850`). XLA shardings require equal shards, so here the
    batch is always completed (wrapping its own samples) and the real sample
    count is recorded in ``remainder`` — ``gather_for_metrics`` drops the
    duplicates, so metrics are dataset-exact either way and ``even_batches``
    has no separate meaning on this path.
    """

    def __iter__(self):
        state = PartialState()
        if state.num_processes == 1:
            yield from super().__iter__()
            return
        self.gradient_state._add_dataloader(self)
        self.end_of_dataloader = False
        try:
            if state.is_main_process:
                def _mark_last():
                    self.end_of_dataloader = True

                source = iter(self.base_loader)
                if self._drop_last:
                    # drop ONLY a trailing short batch, before the last-batch
                    # lookahead, so `last` lands on a batch that is actually
                    # yielded (the epoch-end sync boundary must be observed);
                    # mid-epoch size variation (bucketed samplers) passes through
                    def _full_only(it):
                        first_bs = None
                        prev = None
                        for b in it:
                            if prev is not None:
                                yield prev
                            if first_bs is None:
                                first_bs = find_batch_size(b)
                            prev = b
                        if prev is not None:
                            bs = find_batch_size(prev)
                            if not (bs is not None and first_bs is not None and bs < first_bs):
                                yield prev

                    source = _full_only(source)
                base_it = _PrefetchIterator(source, _mark_last)
            idx = 0
            while True:
                if state.is_main_process:
                    try:
                        batch = next(base_it)
                        payload = [
                            {
                                "stop": False,
                                "batch": recursively_apply(_leaf_to_numpy, batch, test_type=_is_arraylike),
                                "last": self.end_of_dataloader,
                            }
                        ]
                    except StopIteration:
                        payload = [{"stop": True}]
                else:
                    payload = [None]
                broadcast_object_list(payload, from_process=0)
                info = payload[0]
                if info["stop"]:
                    break
                self.end_of_dataloader = info["last"]
                # Slice this host's share of the global batch, completing a
                # ragged batch by wrapping so every process gets equal shapes.
                # The wrap target is aligned to per-process SHARD count too, so
                # downstream _to_global never pads mid-array — all padding sits
                # at the global tail and gather_for_metrics' [:remainder] is
                # exact.
                nproc = state.num_processes
                per_align = 1
                if self.device_placement:
                    mesh = self._data_sharding().mesh
                    shards = math.prod(mesh.shape[a] for a in data_axes(mesh))
                    per_align = max(shards // nproc, 1)
                bs = find_batch_size(info["batch"])
                per = max(-(-bs // nproc), 1) if bs else 0
                per = -(-per // per_align) * per_align
                if bs and per * nproc != bs:
                    if self.end_of_dataloader and self.remainder < 0:
                        self.remainder = bs
                    elif not self.end_of_dataloader and not getattr(self, "_warned_wrap", False):
                        import warnings

                        warnings.warn(
                            f"DataLoaderDispatcher: mid-epoch batch of {bs} samples "
                            f"wrapped to {per * nproc} to fill {nproc} process(es) x "
                            f"{per} per-process shard; the duplicates are NOT tracked "
                            "by gather_for_metrics (only the final batch's remainder "
                            "is). Use batch sizes divisible by the data-axis shard "
                            "count for exact metrics."
                        )
                        self._warned_wrap = True

                def _slice(t):
                    if t.shape[0] != per * nproc:
                        t = t[(np.arange(per * nproc) % t.shape[0])]
                    start = per * state.process_index
                    return t[start : start + per]

                local = recursively_apply(_slice, info["batch"], test_type=_is_arraylike)
                if idx >= self.skip_batches:
                    self.batches_seen_in_epoch = idx + 1
                    yield self._to_global(local)
                idx += 1
        finally:
            self.gradient_state._remove_dataloader(self)
            self.skip_batches = 0


# ------------------------------------------------------------------ factories
def _is_torch_loader(obj: Any) -> bool:
    return type(obj).__module__.startswith("torch.utils.data")


def prepare_data_loader(
    dataloader: Any,
    device_placement: bool = True,
    num_processes: int | None = None,
    process_index: int | None = None,
    split_batches: bool = False,
    put_on_device: bool = True,
    rng_types: list[str] | None = None,
    dispatch_batches: bool | None = None,
    even_batches: bool = True,
    use_seedable_sampler: bool = True,
    mesh=None,
    seed: int = 0,
) -> DataLoaderShard:
    """Shard a dataloader across processes and wrap it to emit global mesh-sharded
    arrays (reference `prepare_data_loader`, `data_loader.py:930-1179`).

    Accepts a torch `DataLoader` (rebuilt around `BatchSamplerShard`, preserving
    collate_fn/workers), or any iterable of batches (wrapped directly).
    """
    state = PartialState()
    num_processes = state.num_processes if num_processes is None else num_processes
    process_index = state.process_index if process_index is None else process_index

    synchronized_sampler: SeedableRandomSampler | None = None

    if _is_torch_loader(dataloader):
        import torch.utils.data as tud

        dataset = dataloader.dataset
        is_iterable = isinstance(dataset, tud.IterableDataset)
        if dispatch_batches is None:
            dispatch_batches = num_processes > 1 and is_iterable
        batch_size = dataloader.batch_size
        if batch_size is None and dataloader.batch_sampler is not None:
            batch_size = getattr(dataloader.batch_sampler, "batch_size", None)
        drop_last = getattr(dataloader, "drop_last", False)
        total_len = len(dataset) if hasattr(dataset, "__len__") else None

        common = dict(
            num_workers=dataloader.num_workers,
            collate_fn=dataloader.collate_fn,
            pin_memory=False,
            timeout=dataloader.timeout,
            worker_init_fn=dataloader.worker_init_fn,
        )

        if is_iterable:
            if num_processes > 1 and not dispatch_batches:
                dataset = IterableDatasetShard(
                    dataset,
                    batch_size=batch_size * num_processes if not split_batches else batch_size,
                    num_processes=num_processes,
                    process_index=process_index,
                    drop_last=drop_last,
                    split_batches=split_batches,
                    seed=seed,
                )
            new_loader = tud.DataLoader(dataset, batch_size=batch_size, drop_last=drop_last, **common)
        else:
            batch_sampler = dataloader.batch_sampler
            sampler = getattr(batch_sampler, "sampler", None)
            if use_seedable_sampler and isinstance(sampler, tud.RandomSampler):
                synchronized_sampler = SeedableRandomSampler(len(dataset), seed=seed)
                batch_sampler = tud.BatchSampler(
                    synchronized_sampler, batch_size=batch_size, drop_last=drop_last
                )
            if num_processes > 1:
                batch_sampler = BatchSamplerShard(
                    batch_sampler,
                    num_processes=num_processes,
                    process_index=process_index,
                    split_batches=split_batches,
                    even_batches=even_batches,
                )
            new_loader = tud.DataLoader(dataset, batch_sampler=batch_sampler, **common)

        per_host_batch = batch_size if (split_batches or num_processes == 1) else batch_size
        global_batch = batch_size if split_batches else (batch_size or 0) * num_processes
        cls = DataLoaderDispatcher if dispatch_batches else DataLoaderShard
        return cls(
            new_loader,
            device_placement=device_placement and put_on_device,
            mesh=mesh,
            rng_types=rng_types,
            synchronized_generator=synchronized_sampler,
            total_dataset_length=total_len,
            total_batch_size=global_batch or per_host_batch,
            even_batches=even_batches,
            _drop_last=drop_last,
        )

    # plain iterable of batches
    cls = DataLoaderDispatcher if dispatch_batches else DataLoaderShard
    return cls(
        dataloader,
        device_placement=device_placement and put_on_device,
        mesh=mesh,
        rng_types=rng_types,
        total_dataset_length=getattr(dataloader, "total_dataset_length", None),
        total_batch_size=getattr(dataloader, "total_batch_size", None),
        even_batches=even_batches,
    )


class SkipBatchSampler:
    """Wrap any batch sampler, skipping its first ``skip_batches`` batches
    (reference `SkipBatchSampler`, `data_loader.py:1221`): the sampler-level
    building block behind `skip_first_batches` for torch loaders whose
    sampler the caller manages directly."""

    def __init__(self, batch_sampler: Any, skip_batches: int = 0):
        self.batch_sampler = batch_sampler
        self.skip_batches = skip_batches
        # forward the nominal size so BatchSamplerShard keeps exact pad math
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    def __iter__(self):
        for i, batch in enumerate(self.batch_sampler):
            if i >= self.skip_batches:
                yield batch

    @property
    def total_length(self) -> int:
        return len(self.batch_sampler)

    def __len__(self) -> int:
        return max(len(self.batch_sampler) - self.skip_batches, 0)


def get_sampler(dataloader: Any):
    """The index sampler driving a (possibly prepared/wrapped) dataloader
    (reference `get_sampler`, `data_loader.py:1199`)."""
    base = getattr(dataloader, "base_loader", dataloader)
    batch_sampler = getattr(base, "batch_sampler", None)
    while batch_sampler is not None and hasattr(batch_sampler, "batch_sampler"):
        batch_sampler = batch_sampler.batch_sampler  # unwrap shard/skip layers
    return getattr(batch_sampler, "sampler", getattr(base, "sampler", None))


def skip_first_batches(dataloader: Any, num_batches: int = 0) -> Any:
    """Resume mid-epoch by skipping the first ``num_batches`` batches
    (reference `data_loader.py:1245-1320`)."""
    if isinstance(dataloader, DataLoaderShard):
        dataloader.skip_batches = num_batches
        return dataloader
    return _SkipIterable(dataloader, num_batches)


class _SkipIterable:
    """Minimal skip wrapper for non-prepared iterables (reference `SkipDataLoader`)."""

    def __init__(self, base: Iterable, skip: int):
        self.base = base
        self.skip = skip

    def __iter__(self):
        for i, batch in enumerate(self.base):
            if i >= self.skip:
                yield batch

    def __len__(self):
        return max(len(self.base) - self.skip, 0)
