"""Query-plan API: compiled predicate programs + execution specs.

This module is the query path's front door.  It owns three things:

1. **Compiled predicate programs** (:func:`compile_predicates` →
   :class:`PredicateProgram`): a batch of heterogeneous predicate
   expression trees compiles into one flat, columnar, jit-able IR —
   per-query instruction rows (op-code + column-slot + operand arrays)
   forming a single pytree of device arrays.  :func:`evaluate_program`
   runs the whole batch as ONE fused on-device pass over a device-resident
   column pack (:class:`PackedColumns`), replacing the legacy
   ``evaluate_batch`` host loop of one traced call per predicate.  The IR
   is a postorder stack machine: leaves push ``(n,)`` bool masks, boolean
   connectives combine the top of a fixed-depth stack.  Op-codes are
   *data*, not trace-time structure, so any mix of predicate shapes in a
   batch shares one compiled program evaluator — the predicate-agnostic
   property ACORN claims, carried down to the execution plan (NaviX and
   the GPU all-in-one index argue the same placement; PAPERS.md).

   Host-only leaves (``RegexMatch``) cannot run on device; they are
   pre-evaluated ONCE per ``(column, pattern)`` into cached auxiliary
   bitmaps (:meth:`AttributeTable.regex_mask`) that ride into the fused
   pass as an ``aux`` input the ``AUX`` op-code indexes.

2. **ExecutionSpec**: a frozen, hashable bundle of the five execution
   knobs (``use_kernel``/``interpret``/``expand_kernel``/
   ``data_parallel``/``corpus_parallel``) that used to thread positionally
   through every search signature.  A *resolved* spec (no ``None`` fields)
   is the compiled-variant cache key component — one object, one hash.

3. **SearchRequest**: queries + predicates (tree list or pre-compiled
   program) + ``k``/``ef``/``route`` as one value, the new call style for
   :meth:`HybridIndex.search` and the serving engine.

Shape discipline: program array widths (instruction count, OneOf operand
width, stack depth) are bucketed (powers of two / multiples of four) so a
steady request stream compiles a handful of program shapes, mirroring the
jit-bucket design of ``core/batched.py``; the bitset operand width is
pinned by the table schema, not the predicates.  ``shape_sig`` exposes
the bucketed shape for variant-cache keys.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from .predicates import (And, AttributeTable, Between, ContainsAny, Equals,
                         Not, OneOf, Or, Predicate, RegexMatch, TruePredicate,
                         keywords_to_bitset)

Array = jax.Array

# ---------------------------------------------------------------------------
# ExecutionSpec — the five knobs as one frozen, hashable value
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ExecutionSpec:
    """How a search executes, independent of what it searches.

    ``use_kernel``      — route distances through the gather_distance
                          Pallas kernel (pure-jnp reference otherwise);
    ``interpret``       — run Pallas kernels in interpret mode (the CPU
                          has no Mosaic backend; tests ask for it);
    ``expand_kernel``   — route neighbor expansion through its Pallas
                          kernel; ``None`` follows ``use_kernel``;
    ``data_parallel``   — query-shard the batch over this many local
                          devices (``None``/``0`` = all, 1 = off);
    ``corpus_parallel`` — corpus-mesh axis size for sharded serving
                          (``None``/``0`` = auto; a single index pins 1).

    Frozen + hashable: a fully *resolved* spec (:meth:`resolve`) is used
    directly as the compiled-variant cache key component.
    """

    use_kernel: bool = False
    interpret: bool = False
    expand_kernel: Optional[bool] = None
    data_parallel: Optional[int] = 1
    corpus_parallel: Optional[int] = None

    def resolved_expand_kernel(self) -> bool:
        return (self.use_kernel if self.expand_kernel is None
                else self.expand_kernel)

    def resolve(self, data_parallel: Optional[int] = None,
                corpus_parallel: Optional[int] = None) -> "ExecutionSpec":
        """Pin every field to a concrete value (cache-key form).

        ``data_parallel``/``corpus_parallel`` override with the mesh shape
        the caller actually resolved (device clamping / mesh fitting are
        caller policy — see ``query_parallel.resolve_data_parallel`` and
        ``corpus_parallel.resolve_corpus_mesh_shape``).
        """
        dp = self.data_parallel if data_parallel is None else data_parallel
        cp = (self.corpus_parallel if corpus_parallel is None
              else corpus_parallel)
        return ExecutionSpec(use_kernel=self.use_kernel,
                             interpret=self.interpret,
                             expand_kernel=self.resolved_expand_kernel(),
                             data_parallel=dp, corpus_parallel=cp)

    def overlay(self, **overrides) -> "ExecutionSpec":
        """A copy with any non-``None`` overrides applied."""
        kept = {k: v for k, v in overrides.items() if v is not None}
        return replace(self, **kept) if kept else self


_KNOB_NAMES = ("use_kernel", "interpret", "expand_kernel", "data_parallel",
               "corpus_parallel")


def resolve_execution_spec(spec: Optional[ExecutionSpec], where: str,
                           base: Optional[ExecutionSpec] = None,
                           stacklevel: int = 3,
                           **legacy) -> ExecutionSpec:
    """Resolve the ``spec=`` argument; reject retired legacy knob kwargs.

    The five per-call knob kwargs (``use_kernel``/``interpret``/
    ``expand_kernel``/``data_parallel``/``corpus_parallel``) were
    deprecated for one release behind a ``DeprecationWarning`` shim and
    are now REMOVED: passing any of them (non-``None``) raises
    ``TypeError`` with a migration hint naming the :class:`ExecutionSpec`
    field.  With no legacy knobs, returns ``spec`` (or ``base``/the
    default spec).
    """
    passed = {k: v for k, v in legacy.items() if v is not None}
    unknown = set(passed) - set(_KNOB_NAMES)
    if unknown:
        raise TypeError(f"{where}: unknown execution knobs {sorted(unknown)}")
    if passed:
        hints = ", ".join(
            f"spec=ExecutionSpec({k}=...)" for k in sorted(passed))
        raise TypeError(
            f"{where}: the legacy execution-knob kwargs {sorted(passed)} "
            f"were removed; pass {hints} instead")
    if spec is not None:
        return spec
    return base or ExecutionSpec()


# ---------------------------------------------------------------------------
# SearchRequest — queries + predicates + k/ef/route as one value
# ---------------------------------------------------------------------------


@dataclass
class SearchRequest:
    """One batch of hybrid-search work.

    ``predicates`` may be a sequence of predicate trees (compiled on
    entry), a pre-compiled :class:`PredicateProgram` (shared across
    shards / repeated calls), or ``None`` for unfiltered ANN
    (``HybridIndex.search`` runs the plain-HNSW substrate; the serving
    engine requires predicates — use ``TruePredicate()`` per query for
    an explicit match-all).  ``k``/``ef`` of ``None`` defer to the
    consumer's default (the call-site kwarg / engine config).  ``route``
    forces the §5.2 router: ``None`` (cost-based), ``"graph"``, or
    ``"prefilter"``.
    """

    xq: Array
    predicates: Union[Sequence[Predicate], "PredicateProgram", None] = None
    k: Optional[int] = None
    ef: Optional[int] = None
    route: Optional[str] = None


# ---------------------------------------------------------------------------
# SearchResult — the one typed result shape for index / engine / runtime
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class SearchResult:
    """Typed result of a hybrid search: one shape for every surface.

    ``ids`` (B, k) int32 global row ids (-1 = empty slot); ``dists``
    (B, k) float32 (``inf`` on empty slots); ``stats`` per-query stat
    arrays keyed by name (e.g. ``dist_comps``, ``selectivity_est``);
    ``routes`` (B,) route actually taken per query (``"graph"`` /
    ``"prefilter"`` / ``"mixed"`` across shards); ``shed``/``degraded``
    (B,) bool — ``shed`` marks requests the runtime refused under
    backpressure, ``degraded`` marks results produced with shards
    missing (including the all-shards-down -1/inf sentinel).

    Registered as a pytree (arrays are leaves; ``legacy_arity`` and
    ``routes`` ride in the aux data) so results slice/concatenate with
    ``tree_map`` like any other value.

    Tuple unpacking keeps working for this release via ``__iter__``:
    ``legacy_arity=2`` yields ``(ids, dists)`` (engine/runtime call
    sites), ``legacy_arity=3`` yields ``(ids, dists, info)`` matching
    the old ``HybridIndex.search`` return.
    """

    ids: Array
    dists: Array
    stats: Dict[str, Any] = field(default_factory=dict)
    routes: Optional[np.ndarray] = None
    shed: Optional[np.ndarray] = None
    degraded: Optional[np.ndarray] = None
    legacy_arity: int = 2

    def tree_flatten(self):
        return ((self.ids, self.dists, self.stats, self.shed,
                 self.degraded),
                (self.routes if self.routes is None
                 else tuple(self.routes), self.legacy_arity))

    @classmethod
    def tree_unflatten(cls, aux, children):
        routes = aux[0] if aux[0] is None else np.asarray(aux[0])
        return cls(ids=children[0], dists=children[1], stats=children[2],
                   shed=children[3], degraded=children[4], routes=routes,
                   legacy_arity=aux[1])

    @property
    def info(self) -> Dict[str, Any]:
        """The legacy ``HybridIndex.search`` info dict, reconstructed."""
        out = dict(self.stats)
        if self.routes is not None:
            out["routes"] = self.routes
        return out

    @property
    def n_queries(self) -> int:
        return int(self.ids.shape[0])

    def __iter__(self):
        yield self.ids
        yield self.dists
        if self.legacy_arity >= 3:
            yield self.info

    def __len__(self) -> int:
        return max(2, self.legacy_arity)

    def __getitem__(self, i):
        return tuple(self)[i]

    def take(self, idx) -> "SearchResult":
        """Row-subset the result (e.g. split a coalesced batch back into
        its member requests)."""
        stats = {name: np.asarray(v)[idx] for name, v in self.stats.items()}
        return SearchResult(
            ids=self.ids[idx], dists=self.dists[idx], stats=stats,
            routes=None if self.routes is None else self.routes[idx],
            shed=None if self.shed is None else self.shed[idx],
            degraded=None if self.degraded is None else self.degraded[idx],
            legacy_arity=self.legacy_arity)

    @staticmethod
    def concatenate(results: Sequence["SearchResult"]) -> "SearchResult":
        """Row-concatenate results (the serve()/runtime merge step).

        Optional fields (routes/shed/degraded) and stats keys must agree
        across parts — all parts come from the same engine surface."""
        if not results:
            raise ValueError("concatenate needs at least one result")
        first = results[0]
        stats = {name: np.concatenate(
                     [np.asarray(r.stats[name]) for r in results])
                 for name in first.stats}

        def _cat(get, np_cat):
            vals = [get(r) for r in results]
            return None if vals[0] is None else np_cat(vals)

        return SearchResult(
            ids=jnp.concatenate([r.ids for r in results]),
            dists=jnp.concatenate([r.dists for r in results]),
            stats=stats,
            routes=_cat(lambda r: r.routes, np.concatenate),
            shed=_cat(lambda r: r.shed, np.concatenate),
            degraded=_cat(lambda r: r.degraded, np.concatenate),
            legacy_arity=first.legacy_arity)


def sentinel_result(b: int, k: int, shed: bool = False,
                    legacy_arity: int = 2) -> SearchResult:
    """The -1/inf empty result set: the all-shards-down degrade shape,
    reused by the runtime's shed-load path (``shed=True``).  Sentinels
    are RESULTS, not exceptions — the serving contract is that overload
    and hard degradation answer in-band."""
    return SearchResult(
        ids=jnp.full((b, k), -1, jnp.int32),
        dists=jnp.full((b, k), jnp.inf, jnp.float32),
        stats=dict(dist_comps=np.zeros((b,), np.int64)),
        routes=np.full((b,), "none"),
        shed=np.full((b,), shed),
        degraded=np.full((b,), not shed),
        legacy_arity=legacy_arity)


# ---------------------------------------------------------------------------
# Table schema + device-resident column pack
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TableSchema:
    """Column-name → slot layout a program compiles against.

    Shards produced by ``AttributeTable.take`` preserve column dicts, so
    one schema (and therefore one compiled program) is valid for the full
    table, every corpus shard, and the selectivity-sketch sample.
    """

    int_cols: Tuple[str, ...]
    bitset_cols: Tuple[str, ...]
    n_keywords: Tuple[int, ...]          # per bitset column
    str_cols: Tuple[str, ...]

    @staticmethod
    def of(table_or_schema) -> "TableSchema":
        if isinstance(table_or_schema, TableSchema):
            return table_or_schema
        t = table_or_schema
        return TableSchema(
            int_cols=tuple(t.int_cols),
            bitset_cols=tuple(t.bitset_cols),
            n_keywords=tuple(t.n_keywords[c] for c in t.bitset_cols),
            str_cols=tuple(t.str_cols))

    @property
    def bitset_words(self) -> int:
        """Packed-word width of the widest bitset column (min 1) — pins
        the CONTAINS operand width schema-wide, so predicate mixes never
        perturb the compiled program shape."""
        return max([(nk + 31) // 32 for nk in self.n_keywords], default=1)

    def int_slot(self, column: str) -> int:
        return self.int_cols.index(column)

    def bitset_slot(self, column: str) -> int:
        return self.bitset_cols.index(column)


class PackedColumns(NamedTuple):
    """Slot-indexed device view of an AttributeTable (a pytree).

    ``ints``    — (C_int, n) int32, stacked in schema slot order;
    ``bitsets`` — (C_bit, n, W) uint32, zero-padded to the schema's
                  ``bitset_words`` width.
    Both carry at least one (zeroed) column so programs over tables with
    no columns of a kind still have well-formed gather targets; dummy
    slots are never referenced by valid instructions.
    """

    ints: Array
    bitsets: Array


def pack_columns(table: AttributeTable,
                 schema: Optional[TableSchema] = None) -> PackedColumns:
    """Stack a table's columns into slot order (cached on the table)."""
    schema = TableSchema.of(table) if schema is None else schema
    cached = table._plan_cache.get("packed")
    if cached is not None and cached[0] == schema:
        return cached[1]
    n = table.n
    w = schema.bitset_words
    if schema.int_cols:
        cols = []
        i32 = np.iinfo(np.int32)
        for c in schema.int_cols:
            col = jnp.asarray(table.int_cols[c])
            if col.dtype != jnp.int32:
                # narrowing must be loud: a wrapped int64 value could
                # silently satisfy an Equals the interpreter rejects
                if bool((col < i32.min).any() | (col > i32.max).any()):
                    raise ValueError(
                        f"int column {c!r} ({col.dtype}) holds values "
                        "outside int32 range — the compiled program "
                        "evaluates int32 slots")
                col = col.astype(jnp.int32)
            cols.append(col)
        ints = jnp.stack(cols)
    else:
        ints = jnp.zeros((1, n), jnp.int32)
    if schema.bitset_cols:
        mats = []
        for c in schema.bitset_cols:
            col = jnp.asarray(table.bitset_cols[c], jnp.uint32)
            if col.shape[1] < w:
                col = jnp.pad(col, ((0, 0), (0, w - col.shape[1])))
            mats.append(col)
        bitsets = jnp.stack(mats)
    else:
        bitsets = jnp.zeros((1, n, w), jnp.uint32)
    packed = PackedColumns(ints=ints, bitsets=bitsets)
    table._plan_cache["packed"] = (schema, packed)
    return packed


def regex_aux(table: AttributeTable,
              regex_leaves: Tuple[Tuple[str, str], ...]) -> Array:
    """Assemble the (A, n) aux bitmap block for a program's regex leaves.

    Each row is the host-evaluated ``(column, pattern)`` mask, served from
    the table's cache (:meth:`AttributeTable.regex_mask`) — the string
    column is rescanned only on first sight of a pattern.  The assembled
    *device* block is itself cached per leaf set (bounded, FIFO), so a
    steady stream of repeated programs re-uploads nothing.  ``A`` is
    padded to at least 1 so the fused pass always has a gather target.
    """
    from .predicates import REGEX_MASK_CACHE_MAX, _fifo_put
    cache = table._plan_cache.setdefault("aux", {})
    block = cache.get(regex_leaves)
    if block is None:
        if not regex_leaves:
            block = jnp.zeros((1, table.n), bool)
        else:
            block = jnp.asarray(np.stack(
                [table.regex_mask(col, pat) for col, pat in regex_leaves]))
        _fifo_put(cache, regex_leaves, block, REGEX_MASK_CACHE_MAX)
    return block


# ---------------------------------------------------------------------------
# The predicate IR
# ---------------------------------------------------------------------------

# op-codes (program *data* — any tree mix shares one compiled evaluator)
OP_NOP = 0       # padding
OP_TRUE = 1      # push all-true
OP_EQ = 2        # push int_col[slot] == lo
OP_ONEOF = 3     # push int_col[slot] ∈ vals[:nval]
OP_BETWEEN = 4   # push lo <= int_col[slot] <= hi
OP_CONTAINS = 5  # push (bitset_col[slot] & qbits) != 0 (any word)
OP_AUX = 6       # push aux[slot] (host-evaluated regex leaf bitmap)
OP_AND = 7       # pop two, push and
OP_OR = 8        # pop two, push or
OP_NOT = 9       # negate top


@jax.tree_util.register_pytree_node_class
@dataclass
class PredicateProgram:
    """A batch of predicate trees as one flat columnar program (a pytree).

    Array fields (the pytree leaves; ``B`` queries, ``L`` instruction
    slots, ``V`` OneOf operand width, ``W`` bitset words):

      ops (B, L) int32; slot (B, L) int32; lo/hi (B, L) int32;
      vals (B, L, V) int32; nval (B, L) int32; qbits (B, L, W) uint32.

    Static metadata (pytree aux data, part of the treedef — changing it
    retraces): ``depth`` (stack depth), ``regex_leaves`` (the ordered
    ``(column, pattern)`` host leaves the ``aux`` input rows map to), and
    ``schema`` — the :class:`TableSchema` the slots were compiled
    against.  ``evaluate`` packs columns BY NAME through that schema, so
    a table whose dict order differs still evaluates correctly, and a
    table missing a referenced column fails loudly (``KeyError``) instead
    of silently reading the wrong slot.
    """

    ops: Array
    slot: Array
    lo: Array
    hi: Array
    vals: Array
    nval: Array
    qbits: Array
    depth: int = 2
    regex_leaves: Tuple[Tuple[str, str], ...] = ()
    schema: Optional[TableSchema] = None

    def tree_flatten(self):
        return ((self.ops, self.slot, self.lo, self.hi, self.vals,
                 self.nval, self.qbits),
                (self.depth, self.regex_leaves, self.schema))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, depth=aux[0], regex_leaves=aux[1],
                   schema=aux[2])

    @property
    def n_queries(self) -> int:
        return int(self.ops.shape[0])

    @property
    def shape_sig(self) -> tuple:
        """Hashable trace-shape signature for variant-cache keys."""
        return (int(self.ops.shape[1]), int(self.vals.shape[2]),
                int(self.qbits.shape[2]), self.depth,
                len(self.regex_leaves))

    def take(self, idx) -> "PredicateProgram":
        """Row-subset the program (e.g. the pre-filter-routed queries)."""
        return PredicateProgram(
            ops=self.ops[idx], slot=self.slot[idx], lo=self.lo[idx],
            hi=self.hi[idx], vals=self.vals[idx], nval=self.nval[idx],
            qbits=self.qbits[idx], depth=self.depth,
            regex_leaves=self.regex_leaves, schema=self.schema)

    @staticmethod
    def concat(programs: Sequence["PredicateProgram"]) -> "PredicateProgram":
        """Row-concatenate programs sharing one admission shape.

        The runtime's coalescing step: requests admitted under the same
        :func:`admission_key` (identical ``shape_sig``/schema/regex
        leaves) concatenate into one program whose batch is exactly the
        member rows, so a coalesced dispatch hits the same compiled
        variant as any other batch of that shape.  Mixing shapes is a
        bug in the grouping layer and fails loudly here.
        """
        if not programs:
            raise ValueError("concat needs at least one program")
        first = programs[0]
        for p in programs[1:]:
            if (p.shape_sig != first.shape_sig
                    or p.regex_leaves != first.regex_leaves
                    or p.schema != first.schema):
                raise ValueError(
                    f"cannot concat programs of different admission "
                    f"shapes: {p.shape_sig} vs {first.shape_sig} "
                    "(group by admission_key before coalescing)")
        if len(programs) == 1:
            return first
        # host-side concatenate: coalescing happens per dispatch with
        # arbitrary row-count splits, and an eager device concatenate
        # would mint a one-off XLA op per novel split shape — numpy keeps
        # the coalescing free and lets the (bucket-shaped) search call be
        # the only jit entry
        cat = np.concatenate
        return PredicateProgram(
            ops=cat([p.ops for p in programs]),
            slot=cat([p.slot for p in programs]),
            lo=cat([p.lo for p in programs]),
            hi=cat([p.hi for p in programs]),
            vals=cat([p.vals for p in programs]),
            nval=cat([p.nval for p in programs]),
            qbits=cat([p.qbits for p in programs]),
            depth=first.depth, regex_leaves=first.regex_leaves,
            schema=first.schema)

    # -- convenience front door ------------------------------------------
    def evaluate(self, table: AttributeTable) -> Array:
        """(B, n) bool pass-masks over ``table`` in one fused jit call.

        Columns are packed by name through the program's compile-time
        schema, so any table carrying the referenced columns evaluates
        correctly regardless of dict order.  The row dimension is padded
        to a power of two before dispatch (padding rows repeat the last
        query; sliced off after), so ragged batch sizes — e.g. the
        per-shard pre-filter-routed subsets, which vary 0..B with
        workload selectivity — reuse O(log B) compiled shapes instead of
        minting one per distinct count."""
        b = self.n_queries
        if b == 0:
            return jnp.zeros((0, table.n), bool)
        pb = max(4, _next_pow2(b))
        prog = self if pb == b else jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jnp.broadcast_to(a[-1:], (pb - b,) + a.shape[1:])]),
            self)
        cols = pack_columns(table, self.schema)
        aux = regex_aux(table, self.regex_leaves)
        return _evaluate_jit(prog, cols.ints, cols.bitsets, aux)[:b]


def admission_key(program: "PredicateProgram", k: int, ef: int,
                  route: Optional[str]) -> tuple:
    """The runtime's admission-queue grouping key.

    Requests whose programs share a bucketed trace shape (``shape_sig``),
    regex-leaf set, schema, and ``k``/``ef``/``route`` coalesce into one
    dispatch: their programs concatenate cleanly
    (:meth:`PredicateProgram.concat`) and the batch hits an
    already-compiled variant — mixed predicate arities land in separate
    groups instead of forcing retraces.
    """
    return (program.shape_sig, program.regex_leaves, program.schema,
            int(k), int(ef), route)


def _bucket_up(x: int, multiple: int, floor: int) -> int:
    return max(floor, -(-x // multiple) * multiple)


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


class _Emitter:
    def __init__(self, schema: TableSchema,
                 regex_slots: Dict[Tuple[str, str], int]):
        self.schema = schema
        self.regex_slots = regex_slots
        self.instrs: List[tuple] = []  # (op, slot, lo, hi, vals, qbits)
        self.sp = 0
        self.max_sp = 0

    def _push(self, op, slot=0, lo=0, hi=0, vals=(), qbits=()):
        self.instrs.append((op, slot, lo, hi, tuple(vals), tuple(qbits)))
        self.sp += 1
        self.max_sp = max(self.max_sp, self.sp)

    def _combine(self, op):
        self.instrs.append((op, 0, 0, 0, (), ()))
        if op != OP_NOT:
            self.sp -= 1

    def emit(self, pred: Predicate) -> None:
        s = self.schema
        if isinstance(pred, TruePredicate):
            self._push(OP_TRUE)
        elif isinstance(pred, Equals):
            self._push(OP_EQ, slot=s.int_slot(pred.column),
                       lo=int(pred.value))
        elif isinstance(pred, OneOf):
            self._push(OP_ONEOF, slot=s.int_slot(pred.column),
                       vals=tuple(int(v) for v in pred.values))
        elif isinstance(pred, Between):
            self._push(OP_BETWEEN, slot=s.int_slot(pred.column),
                       lo=int(pred.lo), hi=int(pred.hi))
        elif isinstance(pred, ContainsAny):
            nk = s.n_keywords[s.bitset_slot(pred.column)]
            q = keywords_to_bitset(pred.keywords, nk)
            self._push(OP_CONTAINS, slot=s.bitset_slot(pred.column),
                       qbits=tuple(int(w) for w in q))
        elif isinstance(pred, RegexMatch):
            key = (pred.column, pred.pattern)
            aux_row = self.regex_slots.setdefault(key, len(self.regex_slots))
            self._push(OP_AUX, slot=aux_row)
        elif isinstance(pred, (And, Or)):
            if not pred.parts:
                raise ValueError(f"{type(pred).__name__} needs >= 1 part")
            op = OP_AND if isinstance(pred, And) else OP_OR
            self.emit(pred.parts[0])
            for p in pred.parts[1:]:
                self.emit(p)
                self._combine(op)
        elif isinstance(pred, Not):
            self.emit(pred.part)
            self._combine(OP_NOT)
        else:
            raise TypeError(f"cannot compile predicate {type(pred)}")


def compile_predicates(preds: Sequence[Predicate],
                       schema) -> PredicateProgram:
    """Compile a batch of predicate trees against a table schema.

    ``schema`` is a :class:`TableSchema` or an :class:`AttributeTable`.
    Instruction count, OneOf operand width, and stack depth are bucketed
    (multiples of 4 / powers of two) so steady workloads reuse a handful
    of program shapes; the bitset operand width comes from the schema
    alone.  Regex leaves are deduplicated across the batch by
    ``(column, pattern)`` into shared aux rows.
    """
    schema = TableSchema.of(schema)
    if len(preds) == 0:
        raise ValueError("compile_predicates needs at least one predicate")
    regex_slots: Dict[Tuple[str, str], int] = {}
    emitters = []
    for p in preds:
        e = _Emitter(schema, regex_slots)
        e.emit(p)
        assert e.sp == 1, "postorder compilation must leave one result"
        emitters.append(e)

    b = len(emitters)
    length = _bucket_up(max(len(e.instrs) for e in emitters), 4, 4)
    depth = max(2, _next_pow2(max(e.max_sp for e in emitters)))
    vmax = max((len(i[4]) for e in emitters for i in e.instrs), default=0)
    vwidth = max(4, _next_pow2(vmax)) if vmax else 4
    w = schema.bitset_words

    ops = np.zeros((b, length), np.int32)
    slot = np.zeros((b, length), np.int32)
    lo = np.zeros((b, length), np.int32)
    hi = np.zeros((b, length), np.int32)
    vals = np.zeros((b, length, vwidth), np.int32)
    nval = np.zeros((b, length), np.int32)
    qbits = np.zeros((b, length, w), np.uint32)
    for qi, e in enumerate(emitters):
        for li, (op, sl, l_, h_, vs, qb) in enumerate(e.instrs):
            ops[qi, li] = op
            slot[qi, li] = sl
            lo[qi, li], hi[qi, li] = l_, h_
            nval[qi, li] = len(vs)
            if vs:
                vals[qi, li, : len(vs)] = vs
            if qb:
                qbits[qi, li, : len(qb)] = qb
    regex_leaves = tuple(sorted(regex_slots, key=regex_slots.get))
    # the columnar IR stays host-side (numpy): row-slicing and
    # concatenation are per-request serving operations where a device
    # array would turn every ``take`` into a traced gather dispatch —
    # the evaluator's jit boundary moves rows on-device exactly once
    return PredicateProgram(
        ops=ops, slot=slot, lo=lo, hi=hi, vals=vals, nval=nval,
        qbits=qbits, depth=depth, regex_leaves=regex_leaves,
        schema=schema)


# ---------------------------------------------------------------------------
# The fused evaluator
# ---------------------------------------------------------------------------


def evaluate_program(prog: PredicateProgram, ints: Array, bitsets: Array,
                     aux: Array, n_valid: Optional[Array] = None) -> Array:
    """Run the whole program batch in one fused pass: (B, n) bool masks.

    ``ints`` (C_int, n) int32, ``bitsets`` (C_bit, n, W) uint32 — a
    :class:`PackedColumns`; ``aux`` (A, n) bool regex-leaf bitmaps.
    ``n_valid`` (scalar int), when given, forces rows >= n_valid to False
    — the padded-row guard for envelope-padded corpus shards, where a
    zero-filled attribute row could otherwise satisfy a predicate the
    real shard never stored.

    Pure jnp, trace-safe: op-codes are data, so one trace serves every
    predicate mix with the same bucketed program shape.  The stack is a
    (B, S, n) bool array; each of the L instruction steps computes the
    candidate leaf values once per query row and one-hot-writes the
    stack at the per-query stack pointer.
    """
    b, length = prog.ops.shape
    n = ints.shape[1]
    s_depth = prog.depth
    stack = jnp.zeros((b, s_depth, n), bool)
    sp = jnp.zeros((b,), jnp.int32)
    srange = jnp.arange(s_depth)

    def _top(st, ptr):
        """stack row at (clamped) ptr: (B, n)."""
        idx = jnp.clip(ptr, 0, s_depth - 1)
        return jnp.take_along_axis(st, idx[:, None, None], axis=1)[:, 0]

    for step in range(length):
        op = prog.ops[:, step]                       # (B,)
        sl = prog.slot[:, step]
        lo = prog.lo[:, step][:, None]
        hi = prog.hi[:, step][:, None]
        col = ints[jnp.clip(sl, 0, ints.shape[0] - 1)]   # (B, n)
        leaf_eq = col == lo
        leaf_bt = (col >= lo) & (col <= hi)
        vs = prog.vals[:, step]                      # (B, V)
        vmask = jnp.arange(vs.shape[1])[None] < prog.nval[:, step][:, None]
        leaf_oneof = ((col[:, :, None] == vs[:, None, :])
                      & vmask[:, None, :]).any(axis=-1)
        bcol = bitsets[jnp.clip(sl, 0, bitsets.shape[0] - 1)]  # (B, n, W)
        qb = prog.qbits[:, step][:, None, :]         # (B, 1, W)
        leaf_ca = ((bcol & qb) != 0).any(axis=-1)
        leaf_aux = aux[jnp.clip(sl, 0, aux.shape[0] - 1)]      # (B, n)
        is_op = op[:, None]
        leaf = jnp.select(
            [is_op == OP_TRUE, is_op == OP_EQ, is_op == OP_ONEOF,
             is_op == OP_BETWEEN, is_op == OP_CONTAINS, is_op == OP_AUX],
            [jnp.ones_like(leaf_eq), leaf_eq, leaf_oneof, leaf_bt,
             leaf_ca, leaf_aux],
            default=jnp.zeros_like(leaf_eq))

        top1 = _top(stack, sp - 1)
        top2 = _top(stack, sp - 2)
        is_leaf = (op >= OP_TRUE) & (op <= OP_AUX)
        value = jnp.where(
            is_leaf[:, None], leaf,
            jnp.where((op == OP_NOT)[:, None], ~top1,
                      jnp.where((op == OP_AND)[:, None], top2 & top1,
                                top2 | top1)))
        wpos = jnp.where(is_leaf, sp,
                         jnp.where(op == OP_NOT, sp - 1, sp - 2))
        active = op != OP_NOP
        write = (srange[None] == wpos[:, None]) & active[:, None]  # (B, S)
        stack = jnp.where(write[:, :, None], value[:, None, :], stack)
        sp = sp + jnp.where(active,
                            jnp.where(is_leaf, 1,
                                      jnp.where(op == OP_NOT, 0, -1)), 0)

    out = stack[:, 0]
    if n_valid is not None:
        out = out & (jnp.arange(n)[None] < n_valid)
    return out


@partial(jax.jit, static_argnames=())
def _evaluate_jit(prog, ints, bitsets, aux):
    return evaluate_program(prog, ints, bitsets, aux)


def evaluate_predicates(preds: Sequence[Predicate],
                        table: AttributeTable) -> Array:
    """One-shot convenience: compile against ``table``'s schema and run
    the fused pass.  The program-compiled, bit-identical replacement for
    :func:`repro.core.predicates.evaluate_batch`."""
    return compile_predicates(preds, table).evaluate(table)
