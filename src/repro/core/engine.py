"""The retrieval engine: one declarative spec, one scorer registry.

Every serve-side follow-up to RecJPQ — PQTopK fused scoring, score-bound
dynamic pruning, popularity-permuted sweeps, warm-threshold floors,
mesh-native permute-then-shard serving — used to be a keyword argument
hand-threaded through six layers (``core/serve`` → ``core/sharded`` →
``kernels/jpq_topk`` → ``models/*`` → ``serve/replica`` → the launch
CLIs).  This module collapses that into:

* ``RetrievalSpec`` — a frozen, hashable description of HOW to serve
  (embedding kind, fused/materialise, backend, tile size, prune/perm/
  warm policies, k, stats).  The spec's hashability IS the jit-cache
  key: two serve configurations compile separately iff their specs
  differ, so adding a strategy can never silently alias a compiled
  function.
* a **scorer registry** — ``register_scorer(name, match, fn)`` entries
  keyed off the spec instead of an if/elif ladder over kwargs.  The
  built-ins cover full/QR materialise-then-top-k, JPQ-fused,
  JPQ-fused-pruned, and the mesh-native permuted+warm path; a new head
  (e.g. the ROADMAP's semantic-ID generative retriever) is one
  ``register_scorer`` call, not six layers of plumbing
  (docs/engine.md has the worked example).
* ``RetrievalEngine`` — binds ``(spec, embedding, params)`` once,
  optionally a catalogue version (the runtime ``PruneState`` /
  permutation), and exposes ``engine.retrieve(h, floor=...)``.
  ``BoundRetrieval`` is the model-level wrapper (history → query vector
  → engine → model post-processing) that ``TwoTower.bind_engine`` /
  ``SeqRecModel.bind_engine`` return and ``serve/replica.py`` jits.
* ``JitCache`` — the engine-owned compiled-dispatch cache keyed
  ``(spec, catalogue version, bucket_len)`` with eviction of retired
  catalogue versions on hot-swap.
* ``spec_from_args`` / ``add_spec_args`` — ONE flag cluster shared by
  ``launch/serve.py`` and ``launch/server.py`` (their defaults had
  drifted), and ``spec_for`` — the kwargs→spec normaliser the
  compatibility shims use.

Everything stays bit-exact: the engine only routes; the strategies call
the same ``sharded.fused_topk_over_codes`` / ``sharded.topk_over_items``
code the pre-engine path called, with the same arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax

from repro import dist
from repro.core import jpq as _jpq
from repro.core import sharded

_VALID_BACKENDS = (None, "pallas", "interpret", "scan")


# ===================================================================== spec

@dataclasses.dataclass(frozen=True)
class RetrievalSpec:
    """Frozen, hashable description of a retrieval configuration.

    Fields are POLICY, not runtime state: ``prune`` says "serve pruned",
    the actual ``PruneState`` is bound on the engine per catalogue
    version; ``warm`` is the EMA decay of the threshold floor policy,
    the per-request floor is a traced argument; ``perm`` names the
    sweep-order policy ("none" / "popularity" / "catalogue"), the
    permutation array lives in the catalogue version.  This split is
    what makes the spec a jit-cache key: everything static is in the
    spec, everything runtime is either bound state (closed over per
    cache entry) or a traced argument.
    """
    kind: str = "jpq"              # embedding kind (or a custom head's)
    k: int = 10
    fused: bool = True
    backend: Optional[str] = None  # pallas | interpret | scan | None
    block_n: Optional[int] = None  # code-tile size override
    prune: bool = False            # score-bound dynamic pruning
    perm: str = "none"             # sweep-order policy
    warm: Optional[float] = None   # ThresholdState EMA decay policy
    stats: bool = False            # append the pruning-stats dict
    beams: Optional[int] = None    # semantic-ID beam width (None: auto)

    def __post_init__(self):
        if not isinstance(self.kind, str) or not self.kind:
            raise ValueError(f"spec kind must be a non-empty string, "
                             f"got {self.kind!r}")
        if int(self.k) < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if self.backend not in _VALID_BACKENDS:
            raise ValueError(
                f"spec backend must be one of {_VALID_BACKENDS}, got "
                f"{self.backend!r}")
        if self.block_n is not None and int(self.block_n) < 1:
            raise ValueError(f"spec block_n must be a positive int or "
                             f"None, got {self.block_n!r}")
        if self.perm != "none" and not self.prune:
            raise ValueError(
                f"perm={self.perm!r} is a pruned-path policy: permuted "
                f"sweeps exist to tighten the pruning threshold early — "
                f"set prune=True or perm='none'")
        if self.warm is not None:
            if not (self.prune and self.fused):
                raise ValueError(
                    "warm floors are a pruned-fused-path feature: the "
                    "floor seeds the pruning threshold, which only "
                    "exists on the fused pruned sweep — set prune=True "
                    "and fused=True, or warm=None")
            if not 0.0 <= float(self.warm) < 1.0:
                raise ValueError(
                    f"warm (EMA decay) must be in [0, 1): {self.warm} "
                    f"(1.0 would freeze the EMA at its first value)")
        if self.stats and not (self.prune and self.fused):
            raise ValueError(
                "stats are a pruned-fused-path feature (skip counts and "
                "the final threshold theta only exist on the pruned "
                "sweep) — set prune=True and fused=True, or stats=False")
        if self.beams is not None and int(self.beams) < 1:
            raise ValueError(
                f"spec beams must be a positive int or None (auto), "
                f"got {self.beams!r}")


def spec_for(emb_or_kind, *, k: int, fused: bool = True,
             backend: Optional[str] = None, block_n: Optional[int] = None,
             prune=None, perm=None, warm_decay: Optional[float] = None,
             stats: bool = False) -> RetrievalSpec:
    """Normalise the legacy ``retrieve_topk``-style kwargs into a spec.

    Reproduces the pre-engine leniency rules exactly: ``prune`` /
    ``perm`` are silently dropped when the path cannot honour them
    (non-JPQ kind or ``fused=False`` — those combinations always fell
    through to the materialise reference), while ``stats`` on an
    incapable path raises (it always did, via the pruned-path guard).
    ``warm_decay`` is never silently dropped: a caller serving a warm
    floor on a path with no pruning threshold is a caller bug, so an
    undeliverable warm policy raises instead of recording ``warm=None``
    (the shims forward it — the round-trip regression in
    ``tests/test_engine.py`` pins this).
    """
    kind = emb_or_kind if isinstance(emb_or_kind, str) \
        else emb_or_kind.cfg.kind
    supports_prune = bool(fused) and kind == "jpq"
    pruned = bool(prune) and supports_prune
    if warm_decay is not None and not pruned:
        raise ValueError(
            "warm floors are pruned-JPQ-fused-path features: this "
            "path has no pruning threshold to seed — serve "
            "kind='jpq' with fused=True and prune=True, or drop the "
            "warm policy")
    return RetrievalSpec(
        kind=kind, k=int(k), fused=bool(fused), backend=backend,
        block_n=block_n, prune=pruned,
        perm="popularity" if (pruned and perm is not None) else "none",
        warm=warm_decay if pruned else None, stats=bool(stats))


# ================================================== flag cluster (CLIs)

def add_spec_args(ap, *, fused_default: bool = True,
                  prune_default: bool = False,
                  perm_default: bool = False) -> None:
    """Register the shared retrieval flag cluster on an argparse parser.

    Both serving CLIs (``launch/serve.py``, ``launch/server.py``) accept
    the SAME flags — ``--warm`` and ``--warm-theta`` are aliases for the
    same dest, so scripts written against either CLI keep working —
    and resolve them through one ``spec_from_args``.  Defaults are
    per-CLI (the batch loop defaults unpruned, the request server
    pruned), but identical explicit flags always resolve to identical
    specs.
    """
    import argparse
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=fused_default,
                    help="fused PQTopK serve path for retrieval archs "
                         "(--no-fused: materialise-then-top-k reference)")
    ap.add_argument("--prune", action=argparse.BooleanOptionalAction,
                    default=prune_default,
                    help="score-bound dynamic pruning of code tiles on "
                         "the fused path (bit-exact; docs/serving.md)")
    ap.add_argument("--perm", action=argparse.BooleanOptionalAction,
                    default=perm_default,
                    help="popularity-permuted pruned sweep (implies the "
                         "permute-then-shard layout under --mesh)")
    ap.add_argument("--warm", "--warm-theta", dest="warm", nargs="?",
                    const=0.9, default=None, type=float, metavar="DECAY",
                    help="EMA warm-start of the pruning threshold "
                         "(core.serve.ThresholdState; default decay 0.9)")
    ap.add_argument("--head", choices=("score", "semantic"),
                    default="score",
                    help="retrieval head: 'score' sweeps the catalogue "
                         "(fused/materialise per the flags above); "
                         "'semantic' decodes items as their m-token "
                         "code sequences (constrained beam search — "
                         "needs a JPQ embedding; docs/serving.md)")
    ap.add_argument("--beams", type=int, default=None, metavar="W",
                    help="semantic-head beam width (default: "
                         "max(32, 4*k), capped at the trie's path "
                         "count — beams >= n_paths is exhaustive and "
                         "bit-matches the materialise scorer)")


def spec_from_args(args, *, kind: str = "jpq", k: Optional[int] = None,
                   stats: Optional[bool] = None) -> RetrievalSpec:
    """Resolve the ``add_spec_args`` flag cluster into a RetrievalSpec.

    Pruning-path policies degrade together, mirroring what the serve
    path can actually honour: a non-JPQ kind or ``--no-fused`` drops
    prune (and with it perm/warm), exactly the old CLIs' behaviour —
    but now in ONE place instead of two drifted copies.  ``stats``
    defaults to "on iff pruned" (the stats dict only exists there).
    ``--head semantic`` rewrites the kind to the semantic-ID head —
    which needs a JPQ embedding underneath (its trie is built from the
    codes table), so a non-JPQ base kind raises; the pruning-path
    policies then degrade exactly as for any non-"jpq" kind.
    """
    if getattr(args, "head", "score") == "semantic":
        if kind != "jpq":
            raise ValueError(
                f"--head semantic decodes JPQ code sequences, so it "
                f"needs a JPQ item embedding — the model's embedding "
                f"kind is {kind!r}")
        kind = "semantic"
    fused = bool(getattr(args, "fused", True))
    prune = bool(getattr(args, "prune", False)) and fused and kind == "jpq"
    perm = "popularity" if (bool(getattr(args, "perm", False)) and prune) \
        else "none"
    warm = getattr(args, "warm", None)
    warm = float(warm) if (warm is not None and prune) else None
    if k is None:
        k = int(getattr(args, "top_k", 10))
    if stats is None:
        stats = prune
    beams = getattr(args, "beams", None)
    return RetrievalSpec(kind=kind, k=int(k), fused=fused, prune=prune,
                         perm=perm, warm=warm, stats=bool(stats),
                         beams=None if beams is None else int(beams))


# ============================================================ registry

# (name, match(spec) -> bool, scorer(engine, params, h, floor)).
# Resolution walks front-to-back, so later registrations — e.g. a test's
# dummy head, or a new production strategy — take precedence without
# touching the built-ins.
_SCORERS: List[Tuple[str, Callable, Callable]] = []


def register_scorer(name: str, match: Callable[[RetrievalSpec], bool],
                    fn: Callable, *, front: bool = True) -> None:
    """Add a scoring strategy.  ``match`` claims specs; ``fn(engine,
    params, h, floor)`` scores a [B, d] query block and returns
    ``(values, ids)`` — plus the stats dict when ``spec.stats``.  New
    entries are consulted first (``front=False`` appends — built-ins)."""
    entry = (str(name), match, fn)
    if front:
        _SCORERS.insert(0, entry)
    else:
        _SCORERS.append(entry)


def unregister_scorer(name: str) -> None:
    _SCORERS[:] = [e for e in _SCORERS if e[0] != name]


def scorer_names() -> Tuple[str, ...]:
    return tuple(e[0] for e in _SCORERS)


def resolve_scorer(spec: RetrievalSpec) -> Tuple[str, Callable]:
    for name, match, fn in _SCORERS:
        if match(spec):
            return name, fn
    raise ValueError(
        f"no scorer strategy matches {spec} — registered: "
        f"{scorer_names()}; register one with "
        f"core.engine.register_scorer(name, match, fn)")


# =========================================================== strategies

def _materialise_scorer(engine, p, h, floor):
    """full/QR (or ``fused=False``) reference: materialise [B, N] scores
    and hierarchical top-k.  No sub-id structure to exploit, so none of
    the pruned-path knobs apply."""
    spec = engine.spec
    if spec.prune or engine.prune is not None:
        raise ValueError(
            f"pruning is a fused-JPQ-path feature (it skips CODE tiles); "
            f"spec {spec} materialises the score matrix — use "
            f"kind='jpq' with fused=True, or drop the prune policy")
    if floor is not None:
        raise ValueError(
            "warm floors / stats are pruned-JPQ-fused-path features: "
            "the materialise path has no pruning threshold to seed — "
            "serve with kind='jpq', fused=True and a prune policy, or "
            "drop the floor")
    scores = engine.emb.logits(p, h)                       # [B, N]
    scores = dist.constrain(scores, ("batch", "items"))
    return sharded.topk_over_items(scores, int(spec.k))


def _jpq_fused_scorer(engine, p, h, floor):
    """JPQ fused PQTopK: partial-score LUT contracted against code
    tiles with a running top-k — pruned (+permuted/warm/mesh-native)
    when the engine carries pruning state.  One implementation serves
    all three fused registry entries: the call into
    ``sharded.fused_topk_over_codes`` is identical to the pre-engine
    path's, which is what keeps the refactor bit-exact."""
    spec = engine.spec
    part = _jpq.partial_scores(p, h)                       # [B, m, b]
    return sharded.fused_topk_over_codes(
        part, p["codes"].value, spec.k, block_n=spec.block_n,
        backend=spec.backend, prune=engine.prune, perm=engine.perm,
        warm=floor, return_stats=spec.stats)


register_scorer(
    "materialise",
    lambda s: not s.fused or s.kind != "jpq",
    _materialise_scorer, front=False)
register_scorer(
    "jpq-fused",
    lambda s: s.fused and s.kind == "jpq" and not s.prune,
    _jpq_fused_scorer, front=False)
register_scorer(
    "jpq-fused-pruned",
    lambda s: (s.fused and s.kind == "jpq" and s.prune
               and s.perm == "none" and s.warm is None),
    _jpq_fused_scorer, front=False)
register_scorer(
    # mesh-native permuted and/or warm-floored pruned serving — the
    # permute-then-shard + threshold-exchange + demotion machinery is
    # mesh-dispatched inside fused_topk_over_codes; the distinct
    # registry entry keeps the strategy surface declarative
    "jpq-pruned-permuted-warm",
    lambda s: (s.fused and s.kind == "jpq" and s.prune
               and (s.perm != "none" or s.warm is not None)),
    _jpq_fused_scorer, front=False)


# ============================================================== engine

class RetrievalEngine:
    """Binds (spec, embedding, params) once; resolves the scorer once.

    ``bind_catalogue`` attaches the runtime artefacts a catalogue
    version carries — the prebuilt ``PruneState`` (or ``True`` for an
    inline build) and an optional sweep permutation — and the version
    number the jit cache keys on.  ``retrieve(h, floor=...)`` flattens
    leading dims, dispatches through the resolved scorer, and restores
    them, exactly like the old ``core.serve.retrieve_topk`` body.
    """

    def __init__(self, spec: RetrievalSpec, emb=None, params=None, *,
                 catalogue=None):
        self.spec = spec
        self.emb = emb
        self.params = params
        self.strategy, self._scorer = resolve_scorer(spec)
        # runtime catalogue state: True = inline PruneState build
        self.prune = True if spec.prune else None
        self.perm = None
        self.version = 0
        if catalogue is not None:
            self.bind_catalogue(catalogue)

    def bind_catalogue(self, catalogue=None, *, prune=None, perm=None,
                       version: int = 0) -> "RetrievalEngine":
        """Attach a catalogue version.  ``catalogue`` duck-types
        ``serve.registry.CatalogueVersion`` (``.state`` / ``.version``);
        a prebuilt state embeds its permutation (permute-then-shard),
        so no separate ``perm`` is taken from it.  Alternatively pass
        ``prune=``/``perm=`` directly (the compatibility-shim path)."""
        if catalogue is not None:
            prune = getattr(catalogue, "state", None)
            version = getattr(catalogue, "version", version)
            perm = None
        if self.spec.prune:
            self.prune = True if prune is None else prune
        else:
            if prune not in (None, False):
                raise ValueError(
                    f"spec {self.spec} declares prune=False but a "
                    f"pruning state was bound — the spec is the jit "
                    f"cache key, so state and policy must agree")
            self.prune = None
            perm = None
        self.perm = perm
        self.version = int(version)
        return self

    def retrieve(self, h, *, params=None, floor=None):
        """h [..., d] query vectors -> (values, ids) [..., min(k, N)]
        (+ the pruning-stats dict when ``spec.stats``)."""
        p = self.params if params is None else params
        lead = h.shape[:-1]
        B = 1
        for s in lead:
            B *= s
        out = self._scorer(self, p, h.reshape(B, -1), floor)
        if self.spec.stats:
            v, i, stats = out
            return v.reshape(*lead, -1), i.reshape(*lead, -1), stats
        v, i = out
        return v.reshape(*lead, -1), i.reshape(*lead, -1)


class BoundRetrieval:
    """Model-level engine binding: raw request (history batch) ->
    results.  ``encode`` maps the request to [B, d] query vectors;
    ``postprocess`` applies model-protocol fix-ups (e.g. SeqRecModel's
    pad/[MASK] demotion + total-order re-rank)."""

    def __init__(self, engine: RetrievalEngine, encode: Callable,
                 postprocess: Optional[Callable] = None):
        self.engine = engine
        self._encode = encode
        self._post = postprocess

    @property
    def spec(self) -> RetrievalSpec:
        return self.engine.spec

    def retrieve(self, request, *, floor=None):
        out = self.engine.retrieve(self._encode(request), floor=floor)
        return out if self._post is None else self._post(out)


class JitCache:
    """Engine-owned compiled-dispatch cache keyed on
    ``(spec, catalogue version, bucket_len)``.

    The spec's hashability is the point: the old replica cache keyed on
    ``(version, bucket_len)`` alone, so any future second strategy on
    the same replica would have silently aliased a compiled function.
    ``evict`` drops retired catalogue versions on hot-swap (keep the
    live + draining version) so the cache stays bounded across swaps.
    """

    def __init__(self):
        self._fns = {}

    @staticmethod
    def key(spec: RetrievalSpec, version: int, bucket_len: int):
        if not isinstance(spec, RetrievalSpec):
            raise TypeError(f"cache keys on RetrievalSpec, got "
                            f"{type(spec).__name__}")
        return (spec, int(version), int(bucket_len))

    def get(self, spec: RetrievalSpec, version: int, bucket_len: int,
            build: Callable[[], Callable]) -> Callable:
        key = self.key(spec, version, bucket_len)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def evict(self, keep_versions) -> int:
        """Drop entries whose catalogue version is not in
        ``keep_versions``; returns the number evicted."""
        keep = {int(v) for v in keep_versions}
        dead = [k for k in self._fns if k[1] not in keep]
        for k in dead:
            del self._fns[k]
        return len(dead)

    def versions(self) -> Tuple[int, ...]:
        return tuple(sorted({k[1] for k in self._fns}))

    def __len__(self) -> int:
        return len(self._fns)

    def __contains__(self, key) -> bool:
        return key in self._fns


# ==================================== catalogue-prep / protocol helpers
# The code below is the core-level facade over kernels.jpq_topk.ops for
# the serving layers (registry, CLIs): tests/test_layering.py forbids
# importing the kernel internals from outside core/, so pruning-state
# preparation routes through here.

def resolve_prune_block_n(N: int, *, shards: int = 0,
                          block_n: Optional[int] = None) -> int:
    """Tile size for a pruning state: an explicit ``block_n`` wins;
    under an S-way mesh whose shards tile N, the divisor-aware
    ``mesh_prune_block_n`` keeps one global state row-sliceable;
    otherwise the unsharded default."""
    from repro.kernels.jpq_topk import ops as _tops
    if block_n:
        return int(block_n)
    if shards and int(shards) > 1 and N % int(shards) == 0:
        return _tops.mesh_prune_block_n(N, int(shards))
    return _tops.prune_block_n(N)


def build_prune_state(codes, b: int, *, shards: int = 0,
                      block_n: Optional[int] = None, perm=None):
    """Build the codes-only presence-mask state ONCE, outside any
    per-request jit (the O(N·m) scatter must never run per request —
    docs/serving.md).  ``perm``: optional [N] sweep order; baked into
    the state (permute-then-shard under a mesh)."""
    from repro.kernels.jpq_topk import ops as _tops
    bn = resolve_prune_block_n(codes.shape[0], shards=shards,
                               block_n=block_n)
    return _tops.prepare_pruning(codes, int(b), bn, perm=perm)


def probe_topk(partial, codes, k: int, *, prune=None):
    """Unsharded fused top-k over a probe LUT — the registry's
    swap-validation primitive (pruned-over-new-state must be
    bit-identical to the unpruned sweep)."""
    from repro.kernels.jpq_topk import ops as _tops
    return _tops.jpq_topk_lut(partial, codes, k, prune=prune)


def rerank_candidates(values, ids, k: int):
    """Stable (value desc, id asc) re-rank of a candidate list,
    truncated to k.  The bit-level sort key reproduces ``lax.top_k``'s
    total order (±0.0 included), so re-ranking masked candidates equals
    a top-k over the masked materialised scores — the SeqRecModel serve
    protocol's final step."""
    from repro.kernels.jpq_topk.jpq_topk import sort_total_order
    vv, ids2 = sort_total_order(values, ids)
    return vv[..., :k], ids2[..., :k]
