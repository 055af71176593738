"""Slot-based cache of per-row serving state with block-granular
accounting: K/V, and beside them whatever else a model keeps for a row.

Two halves, deliberately separated:

  * BlockLedger — pure-host bookkeeping: which slot owns how many
    fixed-size blocks of cache capacity, against a global block budget.
    No jax import, so the alloc/free/leak invariants test in
    microseconds (tests/test_serving.py).
  * KVCache — the device arrays, a named set the MODEL declares
    (serving/decode.state_shapes), every kind [planes, slots, ...]:
    dense, preallocated [planes, slots, max_len, heads, head_dim] K and
    V, and for a model with a recurrent mixer its state and convolution
    window beside them — one ledger, one slot index, one lifetime. The
    model also declares which kinds are POSITIONAL (one entry a position,
    hidden by the row's length: K and V, or the one latent of
    models/latent_moe.py), and which of those are RINGS (K and V of a
    layer that looks back a window and no further, models/window_moe.py:
    a row is ``window`` entries and a place to park, not ``max_len``);
    every other kind is RECURRENT. A
    PLANE is one layer's state; a stack that runs several times over its
    weights (models/looped.py) keeps one per (pass, layer), pass-major,
    so there are more planes than weight layers. Dense rather
    than paged-indirect because the engine decodes every slot every
    step at a static shape (docs/serving.md): a gather through a block
    table buys nothing at this batch geometry, while the dense layout
    keeps the decode step jit-stable (lengths are data, never shape).

The ledger still accounts in blocks (HVD_SERVE_KV_BLOCK tokens each)
so admission can refuse work that would oversubscribe cache capacity
BEFORE it holds a slot — the same failure-loudly-at-the-door policy as
the admission queue.
"""

import math

from ..common import config


class BlockLedger:
    """Host-side block accounting for ``num_slots`` cache rows.

    Each slot may grow to ``max_len`` tokens; capacity is claimed in
    blocks of ``block_size`` tokens against ``total_blocks`` (default:
    exactly enough for every slot at full length — a tighter budget
    models cache-constrained admission).
    """

    def __init__(self, num_slots, max_len, block_size=None,
                 total_blocks=None):
        self.block_size = (config.env_int("SERVE_KV_BLOCK", 16)
                           if block_size is None else block_size)
        if self.block_size <= 0:
            raise ValueError(f"block_size must be > 0, got "
                             f"{self.block_size}")
        self.num_slots = num_slots
        self.max_len = max_len
        self.blocks_per_slot_max = math.ceil(max_len / self.block_size)
        self.total_blocks = (num_slots * self.blocks_per_slot_max
                             if total_blocks is None else total_blocks)
        self._free_slots = list(range(num_slots - 1, -1, -1))
        self._blocks = {}  # slot -> blocks held
        self._lengths = {}  # slot -> valid tokens

    @property
    def blocks_in_use(self):
        return sum(self._blocks.values())

    @property
    def free_slots(self):
        return len(self._free_slots)

    def length(self, slot):
        return self._lengths[slot]

    def _blocks_for(self, length):
        return max(1, math.ceil(length / self.block_size))

    def can_alloc(self, length):
        if not self._free_slots or length > self.max_len:
            return False
        return (self.blocks_in_use + self._blocks_for(length)
                <= self.total_blocks)

    def alloc(self, length):
        """Claim a slot sized for ``length`` tokens; None when slots or
        the block budget are exhausted (admission then rejects)."""
        if not self.can_alloc(length):
            return None
        slot = self._free_slots.pop()
        self._blocks[slot] = self._blocks_for(length)
        self._lengths[slot] = length
        return slot

    def alloc_at(self, slot, length, reserve=None):
        """Claim a SPECIFIC free slot — the engine path, where the
        scheduler owns slot assignment and the ledger must account the
        same row. ``reserve`` claims blocks for a longer whole-life
        length up front (the engine reserves prompt + max_new so a
        request, once admitted, can never be starved mid-stream by a
        later joiner). Raises on a taken slot (desync bug) and on an
        over-budget claim (callers gate on can_alloc first)."""
        if slot in self._blocks:
            raise KeyError(f"alloc_at on taken slot {slot}")
        if slot not in self._free_slots:
            raise KeyError(f"alloc_at on unknown slot {slot}")
        reserve = length if reserve is None else max(reserve, length)
        if not self.can_alloc(reserve):
            raise RuntimeError(
                f"alloc_at({slot}, {length}, reserve={reserve}) over "
                f"budget: {self.blocks_in_use}/{self.total_blocks} "
                f"blocks used")
        self._free_slots.remove(slot)
        self._blocks[slot] = self._blocks_for(reserve)
        self._lengths[slot] = length

    def grow(self, slot, new_length):
        """Extend a slot to ``new_length`` tokens, claiming blocks as
        crossed; False when the budget or max_len refuses (the engine
        must then retire the request, never silently truncate)."""
        if slot not in self._blocks:
            raise KeyError(f"grow on unallocated slot {slot}")
        if new_length > self.max_len:
            return False
        need = self._blocks_for(new_length)
        have = self._blocks[slot]
        if need > have:
            if self.blocks_in_use + (need - have) > self.total_blocks:
                return False
            self._blocks[slot] = need
        self._lengths[slot] = new_length
        return True

    def free(self, slot):
        """Return every block the slot holds. Double-free raises — a
        scheduler bug, not a runtime condition to paper over."""
        if slot not in self._blocks:
            raise KeyError(f"free on unallocated slot {slot}")
        del self._blocks[slot]
        del self._lengths[slot]
        self._free_slots.append(slot)

    def predicted_free_blocks(self, queued_tokens):
        """OOM forecast (docs/memory.md): free blocks AFTER the queue
        drains — free minus what ``queued_tokens`` of not-yet-admitted
        work will claim. Active slots already hold their whole-life
        reservation (alloc_at reserves prompt + max_new at admission),
        so the only future claim left is the queue. ≤0 means the next
        admissions will exhaust the cache: the elasticity pressure
        signal and the router's ``kv_forecast`` shed read this."""
        free = self.total_blocks - self.blocks_in_use
        if not queued_tokens or queued_tokens <= 0:
            return free
        return free - math.ceil(queued_tokens / self.block_size)


class KVCache:
    """The per-slot device arrays of every kind of state, plus their
    ledger.

    ``arrays`` is ``{kind: array}``, every kind ``[planes, slots, ...]``,
    as the model declares them (``serving/decode.state_shapes``), and
    each kind is positional or recurrent BY DECLARATION
    (``serving/decode.positional_kinds``), not by its name:

      * ``positional``: ``[planes, slots, entries, ...]``, one entry a
        position of the row. Attention hides what lies above a row's
        length, so a row that a pass does not decode parks its write
        behind it, a prefill writes the padded prefix and the step
        record's ``kv_bytes`` counts these kinds (``count_reads``). A row
        is ``max_len`` entries (parked at ``max_len - 1``), except of the
        kinds in ``ring``: there it is ``window`` entries, position ``p``
        at ``p mod window``, and a place to park OUTSIDE them (index
        ``window``), so that a row never holds more than its last
        ``window`` tokens and a decode pass never reads more. Classes
        differ in their number of planes too: a model's full layers and
        its window layers each stack their own. ``k`` and ``v`` ``[planes, slots, max_len,
        kv_heads, head_dim]`` for a dense, a looped and a hybrid model
        (``.k``/``.v`` read and rebind them; ``planes`` is the layers,
        times the passes of a looped stack); ``latent`` ``[planes, slots,
        max_len, 1, lanes]`` for a model with latent attention: ONE kind,
        key and value at once, and no per-head K or V of a cached token
        anywhere.
      * ``recurrent``: one state a row, with nowhere to park: a pass
        leaves it bit for bit for the rows it does not decode. ``ssm``
        (the recurrent state) and ``conv`` (the convolution's window) of
        a model with a recurrent mixer.

    A slot owns its row of EVERY kind for one
    lifetime: a prefill writes them all whole (K/V up to the padded
    prompt), each decode step advances them all, and retiring the slot
    frees them together — nothing of an occupant outlives its slot,
    because the next prefill overwrites every kind.

    The arrays are updated in place: the engine's programs that rewrite
    them (``engine._decode_jit``, ``engine._write_slot``) take the whole
    set as DONATED input and return it aliased to the same device memory.
    The invariant that makes that safe: ``arrays`` here holds the ONLY
    references, and the engine rebinds it in the statement that makes
    the call (one engine, single-threaded step loop). An array that went
    into such a call is dead after it (``is_deleted()``), so code that
    wants a snapshot copies BEFORE the step, and after an exception
    raised by one of those calls the contents are gone: build a new
    engine. Shapes, dtypes and shardings of the current arrays stay
    readable at any time (``per_chip_bytes``, lowering).

    ``vector_sharding`` is where a ``[slots]`` vector of the engine's
    goes to sit beside the cache (the device's ids, positions,
    temperatures, rows): the cache's device, or replicated over the mesh.
    """

    def __init__(self, cfg, num_slots, max_len=None, block_size=None,
                 total_blocks=None, mesh=None):
        import jax.numpy as jnp
        from . import decode
        max_len = cfg.max_seq_len if max_len is None else max_len
        self.ledger = BlockLedger(num_slots, max_len,
                                  block_size=block_size,
                                  total_blocks=total_blocks)
        shapes = decode.state_shapes(cfg, num_slots, max_len)
        # COMMITTED to the device a new array lands on, from the start: a
        # program's results are committed as soon as one argument is, and
        # an array that is committed in one call and not in the next is
        # another signature to jit (a second compile of the same program)
        self.vector_sharding = jnp.zeros((), jnp.int32).sharding
        self.arrays = {kind: jnp.zeros(a.shape, a.dtype,
                                       device=self.vector_sharding)
                       for kind, a in shapes.items()}
        # what the model declares: kinds with one entry a position (a row
        # that does not decode parks its write at max_len - 1), and the
        # kinds a decode pass must not touch for such a row
        self.positional = tuple(k for k in decode.positional_kinds(cfg)
                                if k in shapes)
        self.recurrent = tuple(sorted(set(shapes) - set(self.positional)))
        # the positional kinds whose rows are rings of ``window`` entries
        self.ring = tuple(k for k in decode.ring_kinds(cfg) if k in shapes)
        self.window = cfg.window if self.ring else None
        if mesh is not None:
            # Tensor-parallel serving (docs/mesh.md): K and V gain a
            # head-sharded NamedSharding over the mesh's tp axis, so each
            # chip holds heads/tp of the cache — the per-chip memory win
            # that lets one replica front a model bigger than a chip.
            # Replicated when tp doesn't divide the cache's heads.
            from ..parallel import mesh as mesh_lib
            if self.recurrent:
                raise NotImplementedError(
                    "a cache with recurrent state has no sharding over a "
                    "mesh yet (mixer heads and groups over tp: ROADMAP R2)")
            if self.positional != ("k", "v"):
                raise NotImplementedError(
                    f"a {type(cfg).__name__}'s cache "
                    f"({', '.join(self.positional)}) has no sharding over a "
                    "mesh yet: a latent is one key head, which tp cannot "
                    "split (ROADMAP R4), and a cache of two classes wants "
                    "one spec a class (ROADMAP R3)")
            spec = mesh_lib.kv_cache_spec(shapes["k"].shape[3], mesh)
            self.k, self.v = mesh_lib.device_put_tree(
                (self.k, self.v), (spec, spec), mesh)
            self.vector_sharding = mesh_lib.named_sharding(None, mesh)
        self.max_len = max_len
        # the blocking under which decode attention reads a row of each
        # class, and the bytes one block of ONE slot holds over the class's
        # planes: what ``count_reads`` multiplies
        from ..ops.flash_attention import decode_block
        # layers that read each ``max_len`` plane in a step: 1, or what a
        # model whose layers SHARE a plane declares (models/sambay.py)
        self.readers = decode.plane_readers(cfg)
        self._reads = []  # (block, bytes a block, entries a row may hold)
        for kinds in (self._full, self.ring):
            if kinds:
                entries = self.arrays[kinds[0]].shape[2]
                block = decode_block(entries)
                times = 1 if kinds is self.ring else self.readers
                self._reads.append(
                    (block, times * self._block_bytes(kinds, block),
                     self.window if kinds is self.ring else max_len))

    @property
    def _full(self):
        """The positional kinds whose rows are ``max_len`` long."""
        return tuple(k for k in self.positional if k not in self.ring)

    @property
    def k(self):
        return self.arrays["k"]

    @k.setter
    def k(self, value):
        self.arrays["k"] = value

    @property
    def v(self):
        return self.arrays["v"]

    @v.setter
    def v(self, value):
        self.arrays["v"] = value

    @property
    def planes(self):
        """Planes of the positional kinds: one a layer, or one a (pass,
        layer) of a stack that runs several times; over both classes where
        a model has window layers beside full ones."""
        return sum(self.arrays[kinds[0]].shape[0]
                   for kinds in (self._full, self.ring) if kinds)

    def bytes_by_kind(self):
        """{kind: bytes resident on ONE chip} over all its planes (the
        shard shape under the array's committed sharding; the full array
        when unsharded)."""
        import numpy as np
        out = {}
        for kind, arr in self.arrays.items():
            sharding = getattr(arr, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                shape = sharding.shard_shape(arr.shape)
            else:
                shape = arr.shape
            out[kind] = int(np.prod(shape)) * arr.dtype.itemsize
        return out

    def per_chip_bytes(self):
        """Bytes of cache (every kind) resident on ONE chip: halves
        under tp=2 (tests/test_mesh_plane.py)."""
        return sum(self.bytes_by_kind().values())

    def row_state_bytes(self):
        """Bytes of recurrent state (every kind that is not positional)
        ONE slot holds over all planes: what a decode step reads and
        writes again for each row it advances."""
        by_kind = self.bytes_by_kind()
        return sum(by_kind[kind] for kind in self.recurrent) \
            // self.num_slots

    def _block_bytes(self, kinds, block):
        by_kind = self.bytes_by_kind()
        return sum(by_kind[kind] * block
                   // (self.num_slots * self.arrays[kind].shape[2])
                   for kind in kinds)

    def kv_block_bytes(self, block):
        """Bytes of the positional kinds of ``max_len`` a row (K and V, or
        the latent) that ``block`` positions of ONE slot hold over all
        their planes (on one chip): what a decode step streams for each
        block of a row it reads (every pass of a looped stack reads its
        own planes). A ring's blocks are ``ring_block_bytes``."""
        return self._block_bytes(self._full, block)

    def ring_block_bytes(self, block):
        """``kv_block_bytes`` of the kinds in ``ring``, over their planes:
        0 for a model without window layers."""
        return self._block_bytes(self.ring, block)

    def count_reads(self, rec, lengths):
        """Into the step record ``rec``: ``kv_bytes``, what a decode pass
        over rows of ``lengths`` has to stream of the positional kinds,
        each row in whole blocks (``ops/flash_attention.decode_block``) up
        to its length, and of a ring up to ``min(length, window)``; and,
        of a model with rings only, ``window_kv_bytes``: the rings' part. A
        ``max_len`` plane that ``readers`` layers read is counted once a
        READER (it is held once), and of such a model only,
        ``shared_kv_bytes`` is that part."""
        parts = [nbytes * sum(-(-min(n, most) // block) for n in lengths)
                 for block, nbytes, most in self._reads]
        rec.count("kv_bytes", sum(parts))
        if self.ring:  # the last class
            rec.count("window_kv_bytes", parts[-1])
        if self.readers > 1:  # the first class, every reader's pass
            rec.count("shared_kv_bytes", parts[0])

    @property
    def num_slots(self):
        return self.ledger.num_slots
