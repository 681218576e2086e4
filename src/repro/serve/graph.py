"""Wave-batched graph-analytics serving over the ``repro.core`` engines.

The target workload is the ROADMAP's "many small molecule graphs per
call": a stream of independent little CC / spanning-forest / tree-
analytics requests that would each waste an accelerator dispatch (and,
worse, a compilation per odd shape) if issued alone. The engine applies
the paper's central lesson -- keep device work branch-free and
shape-static so irregular graph inputs never force recompilation -- to
serving:

* requests queue up and are admitted in FIFO order into WAVES under a
  node/edge budget (``serve/waves.WaveScheduler``, the same outer loop
  as the LM token engine);
* each wave is packed into ONE disjoint-union graph by node/edge offset
  packing -- request i's nodes become ``[node_off[i], node_off[i] +
  n_i)`` -- then padded to a power-of-two **capacity bucket**
  (``core/frontier.next_pow2`` on nodes and edges; pad nodes are
  isolated, pad edges are inert (0, 0) self-loops, and the analytics
  stage pads its forest-edge buffer to the node capacity so the tour
  ranks at the fixed ``2 * node_cap`` arc capacity of
  ``trees/tour.tour_capacity``'s convention);
* the packed union runs through the existing engines as one batched
  device program per wave stage -- ``connected_components`` /
  ``spanning_forest`` / ``tree_analytics`` with ``dedup=False`` so
  shapes stay bucket-static -- and results are unpacked per request by
  offset.

**Bit-exactness.** CC, spanning forests, and Euler-tour analytics over
a disjoint union decompose per component: every SV hook compares labels
only within a component, labels are per-request node ids shifted by the
request's node offset (min node id is offset-shifted), the recorded
hook edges of request i are exactly its solo hook edges shifted, and
the tour's stable source-sort preserves each request's arc order. Pad
nodes are isolated self-components, pad self-loop edges can never hook,
and ``record_hooks`` / extra converged rounds are label-neutral -- so
every unpacked result is bit-identical to issuing the request alone
with the same engine knobs (asserted in ``tests/test_serve_graph.py``;
per-request ``rounds`` is the one quantity that does NOT decompose --
the union runs to the slowest member -- so it is reported per wave, not
per request).

**Compile accounting.** All device programs in a wave are keyed only by
the wave's ``(stage, node_cap, edge_cap)`` bucket, so the jit caches
compile once per bucket and every later wave in that bucket reuses
them. ``engine="auto"`` resolves to ``"dense"`` on a single device: the
auto dispatch's Afforest sampling policy keys on edge density, which
packing changes, and its frontier ladder adds data-dependent inner
bucket compiles -- both would break the serve path's bit-exactness and
compile-count guarantees. Any explicitly pinned engine is honoured
(the frontier/sharded engines stay bit-exact; their host-driven ladders
add at most log2(edge_cap) bounded extra compiles per bucket).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.components import check_choice
from repro.core.frontier import next_pow2
from repro.obs import trace
from repro.serve.waves import WaveScheduler

# Request kinds. The first three form a pipeline-stage chain -- each
# stage subsumes the ones before it, so a mixed wave runs the deepest
# stage any member needs (record_hooks and the tour stages are
# label-neutral by construction). "sssp" and "pagerank" are OUTSIDE
# the chain: each runs a different device program (relax-min over
# weighted edges; add-monoid mass push), so ``_next_wave`` packs them
# only with their own kind -- stage promotion never mixes families.
KINDS = ("cc", "forest", "analytics", "sssp", "pagerank")
_STAGE = {
    k: i for i, k in enumerate(KINDS) if k not in ("sssp", "pagerank")
}


def _family(kind: str) -> str:
    """Wave-packing family: kinds that can share one device program."""
    return kind if kind in ("sssp", "pagerank") else "cc-chain"


@dataclass
class GraphResult:
    """Per-request outputs, unpacked to request-local node ids.

    ``labels``/``num_components`` are filled for every kind in the
    cc-chain family; ``edge_u``/``edge_v`` (the spanning forest, in
    solo edge order) from kind ``"forest"`` up; the tree-analytics
    arrays only for ``"analytics"``. Kind ``"sssp"`` instead fills
    ``dist``/``pred``/``sources``: one row per source, ``+inf`` /
    ``-1`` for unreachable nodes. Kind ``"pagerank"`` fills only
    ``scores``: per-node float32 PageRank mass at the engine's fixed
    iteration count (``pagerank_iters``).
    """

    labels: np.ndarray | None = None
    num_components: int = 0
    edge_u: np.ndarray | None = None
    edge_v: np.ndarray | None = None
    parent: np.ndarray | None = None
    depth: np.ndarray | None = None
    subtree_size: np.ndarray | None = None
    preorder: np.ndarray | None = None
    postorder: np.ndarray | None = None
    dist: np.ndarray | None = None  # (num_sources, n) float32
    pred: np.ndarray | None = None  # (num_sources, n) int32 parent tree
    sources: np.ndarray | None = None  # the request's source nodes
    scores: np.ndarray | None = None  # (n,) float32 pagerank mass


@dataclass
class GraphRequest:
    uid: int
    src: np.ndarray
    dst: np.ndarray
    num_nodes: int
    kind: str = "analytics"
    # weighted-kind inputs: per-edge weights (None = unit) for sssp /
    # pagerank and the sssp source nodes (None = [0]); rejected on
    # kinds that cannot consume them.
    weights: np.ndarray | None = None
    sources: np.ndarray | None = None
    result: GraphResult | None = None
    done: bool = False
    failed: bool = False  # quarantined by the containment layer
    error: str | None = None  # captured failure, when failed

    @property
    def num_edges(self) -> int:
        return int(len(self.src))


@dataclass
class WaveRecord:
    """Deterministic per-wave accounting (benchmarks/graph_serve)."""

    requests: int
    stage: str
    num_nodes: int  # live union nodes
    num_edges: int  # live union edges
    node_cap: int
    edge_cap: int
    new_bucket: bool  # first wave in this (stage, node_cap, edge_cap)
    rounds: int  # SV/relax rounds of the union run (max over members)
    src_cap: int = 0  # sssp waves: padded source-row capacity

    def publish(
        self, registry=None, prefix: str = "serve.graph.wave"
    ) -> None:
        """Publish into the metrics registry (``repro.obs.metrics``):
        counters accumulate across waves, so ``.requests`` is the
        engine's served-request total and ``.new_bucket`` its bucket
        compiles."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


class GraphServeEngine(WaveScheduler):
    """Admit many small graph requests; serve each wave as one padded
    batched engine call. See the module docstring for the packing /
    bucketing / exactness model and ``docs/serving.md`` for knobs.

    * ``max_requests`` (default 16), ``max_nodes`` (4096), ``max_edges``
      (16384) -- wave admission budget; a single request beyond the
      node/edge budget is rejected at ``submit`` (never silently
      dropped later).
    * ``min_nodes`` (64) / ``min_edges`` (128) -- bucket floor, so tiny
      waves share one small-bucket compilation instead of one per size.
    * ``max_sources`` (8) -- per-request source budget for
      ``kind="sssp"`` requests; a wave's source rows pack into a
      ``src_cap`` power-of-two bucket dimension (see
      ``_run_sssp_wave``). sssp waves map ``engine="auto"`` to
      ``"dense"`` like CC waves and reject ``mesh=`` /
      ``engine="sharded_frontier"`` at submit.
    * ``damping`` (0.85) / ``pagerank_iters`` (None =
      ``pagerank_iter_bound(damping, DEFAULT_TOL)``) -- the
      engine-wide ``kind="pagerank"`` knobs. PageRank serving always
      runs the DENSE fixed-iteration engine at exactly
      ``pagerank_iters`` iterations: a tolerance-driven stop would
      run every wave to its slowest member's iteration count, making
      a request's scores depend on its wave-mates. Fixed iterations
      keep batched == solo bit-exact (see ``_run_pagerank_wave``).
    * ``engine=`` / ``rank_engine=`` / ``kernel_impl=`` /
      ``num_splitters=`` / ``mesh=`` and any extra engine kwargs
      (``hook_impl=``, ``exchange=``, ``min_bucket=``, ...) dispatch
      exactly as in ``repro.core`` (full matrix: ``docs/engines.md``),
      except ``engine="auto"`` resolves to ``"dense"`` on one device
      (see module docstring) and the sampling pre-pass
      (``sample_rounds``) is rejected: it re-roots components by edge
      density, which packing changes -- it would break batched == solo.
    * ``max_retries=`` / ``on_failure=`` (``"quarantine"`` default,
      ``"raise"``) / ``fault_plan=`` -- the containment knobs
      (``serve/waves.py``; failure semantics in ``docs/serving.md``).
      An OOM-shaped wave failure permanently caps the packing budget to
      half the failing bucket and re-packs smaller waves; a request is
      only failed when it exhausts the device alone.
    """

    def __init__(
        self,
        *,
        max_requests: int = 16,
        max_nodes: int = 4096,
        max_edges: int = 16384,
        min_nodes: int = 64,
        min_edges: int = 128,
        max_sources: int = 8,
        damping: float = 0.85,
        pagerank_iters: int | None = None,
        engine: str = "auto",
        rank_engine: str = "auto",
        kernel_impl: str = "auto",
        num_splitters: int | None = None,
        mesh=None,
        max_retries: int = 1,
        on_failure: str = "quarantine",
        fault_plan=None,
        **engine_kwargs,
    ):
        import repro.core as core
        from repro.core.list_ranking import KERNEL_IMPLS
        from repro.core.pagerank import DEFAULT_TOL, pagerank_iter_bound
        from repro.trees.compute import RANK_ENGINES

        check_choice("engine", engine, core._CC_ENGINES)
        check_choice("rank_engine", rank_engine, RANK_ENGINES)
        check_choice("kernel_impl", kernel_impl, KERNEL_IMPLS)
        bad = {
            "sample_rounds", "seed", "dedup", "record_hooks", "with_stats",
        } & set(engine_kwargs)
        if bad:
            raise ValueError(
                f"{sorted(bad)} are not servable knobs: the serve path "
                "fixes dedup/record_hooks itself and the sampling "
                "pre-pass would break batched == solo bit-exactness"
            )
        super().__init__(
            max_retries=max_retries, on_failure=on_failure,
            fault_plan=fault_plan,
        )
        self.max_requests = max_requests
        self.max_nodes = max_nodes
        self.max_edges = max_edges
        self.min_nodes = min_nodes
        self.min_edges = min_edges
        self.max_sources = max_sources  # per-request sssp source budget
        # PageRank serve knobs are engine-wide (wave-uniform): every
        # request in a pagerank wave runs the same damping at the same
        # fixed iteration count, so the resolved count is pinned HERE.
        # pagerank_iter_bound also validates damping in (0, 1).
        self.damping = float(damping)
        default_iters = pagerank_iter_bound(self.damping, DEFAULT_TOL)
        self.pagerank_iters = (
            default_iters if pagerank_iters is None else int(pagerank_iters)
        )
        if self.pagerank_iters < 1:
            raise ValueError("pagerank_iters must be >= 1")
        # Degradation caps (permanent, only ever lowered): the packing
        # budget after OOM-shaped failures; see _degrade.
        self._node_budget = max_nodes
        self._edge_budget = max_edges
        if engine == "auto" and mesh is None and jax.device_count() == 1:
            engine = "dense"
        self.engine = engine
        self.rank_engine = rank_engine
        self.kernel_impl = kernel_impl
        self.num_splitters = num_splitters
        self.mesh = mesh
        self.engine_kwargs = dict(engine_kwargs)
        self.wave_records: list[WaveRecord] = []
        self._buckets: set[tuple[str, int, int]] = set()

    # -- deterministic counters (guarded by benchmarks/run.py --check) --
    @property
    def bucket_compiles(self) -> int:
        """Distinct (stage, node_cap, edge_cap) buckets instantiated --
        each is one set of jit-cache entries, reused by every later
        wave in the bucket."""
        return len(self._buckets)

    @property
    def requests_per_wave(self) -> float:
        recs = self.wave_records
        return sum(r.requests for r in recs) / len(recs) if recs else 0.0

    @property
    def node_pad_waste(self) -> float:
        """Padded node slots that carried no request, as a fraction."""
        recs = self.wave_records
        cap = sum(r.node_cap for r in recs)
        return 1.0 - sum(r.num_nodes for r in recs) / cap if cap else 0.0

    @property
    def edge_pad_waste(self) -> float:
        recs = self.wave_records
        cap = sum(r.edge_cap for r in recs)
        return 1.0 - sum(r.num_edges for r in recs) / cap if cap else 0.0

    # ------------------------------------------------------------------
    def submit(self, req: GraphRequest):
        """Validate and enqueue. Rejections happen HERE, loudly -- a
        request that could never fit a wave must not reach the wave
        loop (the LM engine's overlong-prompt lesson)."""
        check_choice("kind", req.kind, KINDS)
        if req.num_nodes < 1:
            raise ValueError(f"request {req.uid}: num_nodes must be >= 1")
        req.src = np.asarray(req.src, np.int32).ravel()
        req.dst = np.asarray(req.dst, np.int32).ravel()
        if req.src.shape != req.dst.shape:
            raise ValueError(
                f"request {req.uid}: src/dst length mismatch "
                f"({req.src.shape} vs {req.dst.shape})"
            )
        if req.num_nodes > self.max_nodes or req.num_edges > self.max_edges:
            raise ValueError(
                f"request {req.uid}: {req.num_nodes} nodes / "
                f"{req.num_edges} edges exceeds the wave budget "
                f"(max_nodes={self.max_nodes}, max_edges={self.max_edges})"
            )
        if req.num_edges and (
            int(min(req.src.min(), req.dst.min())) < 0
            or int(max(req.src.max(), req.dst.max())) >= req.num_nodes
        ):
            raise ValueError(
                f"request {req.uid}: edge endpoints outside "
                f"[0, {req.num_nodes})"
            )
        if req.kind == "sssp":
            self._validate_sssp(req)
        elif req.kind == "pagerank":
            self._validate_pagerank(req)
        elif req.weights is not None or req.sources is not None:
            raise ValueError(
                f"request {req.uid}: weights/sources are only consumed "
                "by the sssp/pagerank kinds"
            )
        super().submit(req)

    def _validate_sssp(self, req: GraphRequest) -> None:
        """Normalize + validate the sssp-only request fields, loudly."""
        if self.mesh is not None or self.engine == "sharded_frontier":
            raise ValueError(
                f"request {req.uid}: sssp waves run the single-device "
                "relax engines; drop mesh= / engine='sharded_frontier'"
            )
        extra = set(self.engine_kwargs) - {"min_bucket"}
        if extra:
            raise ValueError(
                f"request {req.uid}: {sorted(extra)} are not sssp "
                "engine knobs (only min_bucket= carries over)"
            )
        if req.weights is None:
            w = np.ones(req.num_edges, np.float32)  # unit weights: BFS
        else:
            w = np.asarray(req.weights, np.float32).ravel()
        if w.shape != req.src.shape:
            raise ValueError(
                f"request {req.uid}: weights length {w.shape} != edge "
                f"count {req.src.shape}"
            )
        if req.num_edges and (not np.isfinite(w).all() or bool((w < 0).any())):
            raise ValueError(
                f"request {req.uid}: sssp weights must be finite and >= 0"
            )
        req.weights = w
        if req.sources is None:
            s = np.zeros(1, np.int32)
        else:
            s = np.atleast_1d(np.asarray(req.sources, np.int32)).ravel()
        if not 1 <= len(s) <= self.max_sources:
            raise ValueError(
                f"request {req.uid}: {len(s)} sources exceeds the "
                f"per-request budget (1..max_sources={self.max_sources})"
            )
        if int(s.min()) < 0 or int(s.max()) >= req.num_nodes:
            raise ValueError(
                f"request {req.uid}: sources outside [0, {req.num_nodes})"
            )
        req.sources = s

    def _validate_pagerank(self, req: GraphRequest) -> None:
        """Normalize + validate the pagerank-only request fields."""
        if self.mesh is not None or self.engine == "sharded_frontier":
            raise ValueError(
                f"request {req.uid}: pagerank waves run the single-"
                "device dense engine; drop mesh= / "
                "engine='sharded_frontier'"
            )
        if self.engine_kwargs:
            raise ValueError(
                f"request {req.uid}: {sorted(self.engine_kwargs)} are "
                "not pagerank engine knobs (the dense fixed-iteration "
                "engine takes only damping= / pagerank_iters=)"
            )
        if req.sources is not None:
            raise ValueError(
                f"request {req.uid}: sources is an sssp-only field "
                "(pagerank scores every node)"
            )
        if req.weights is None:
            w = np.ones(req.num_edges, np.float32)  # unit weights
        else:
            w = np.asarray(req.weights, np.float32).ravel()
        if w.shape != req.src.shape:
            raise ValueError(
                f"request {req.uid}: weights length {w.shape} != edge "
                f"count {req.src.shape}"
            )
        if req.num_edges and (not np.isfinite(w).all() or bool((w < 0).any())):
            raise ValueError(
                f"request {req.uid}: pagerank weights must be finite "
                "and >= 0"
            )
        req.weights = w

    def _next_wave(self) -> list[GraphRequest]:
        """FIFO greedy packing under the node/edge budget (the
        degradation caps, when an OOM has lowered them). A wave stays
        within one packing FAMILY (cc-chain vs sssp): the families run
        different device programs, so mixing them would force both
        into one wave's single batched call. FIFO order is preserved
        inside the wave; a family boundary closes the wave (no
        reordering past it, so completion order stays deterministic)."""
        wave: list[GraphRequest] = []
        nodes = edges = 0
        while self.queue and len(wave) < self.max_requests:
            r = self.queue[0]
            if wave and _family(r.kind) != _family(wave[0].kind):
                break
            if wave and (
                nodes + r.num_nodes > self._node_budget
                or edges + r.num_edges > self._edge_budget
            ):
                break
            wave.append(self.queue.pop(0))
            nodes += r.num_nodes
            edges += r.num_edges
        return wave

    def _wave_caps(self, wave: list[GraphRequest]) -> tuple[int, int]:
        """The capacity bucket a wave maps to (same math as _run_wave)."""
        n_union = sum(r.num_nodes for r in wave)
        m_union = sum(r.num_edges for r in wave)
        node_cap = max(self.min_nodes, next_pow2(n_union))
        edge_cap = max(self.min_edges, next_pow2(max(m_union, 1)))
        return node_cap, edge_cap

    def _degrade(
        self, wave: list[GraphRequest], exc: Exception
    ) -> list[list[GraphRequest]] | None:
        """OOM-shaped failure: permanently cap the packing budget to
        half the failing bucket and re-pack this wave under it. A
        singleton wave cannot shrink (its own bucket IS its size), so
        it returns None and quarantines; lone requests larger than the
        capped budget become singleton sub-waves and meet the same
        fate if they still exhaust the device."""
        if len(wave) == 1:
            return None
        node_cap, edge_cap = self._wave_caps(wave)
        self._node_budget = min(
            self._node_budget, max(self.min_nodes, node_cap // 2)
        )
        self._edge_budget = min(
            self._edge_budget, max(self.min_edges, edge_cap // 2)
        )
        subs: list[list[GraphRequest]] = []
        cur: list[GraphRequest] = []
        nodes = edges = 0
        for r in wave:
            if cur and (
                nodes + r.num_nodes > self._node_budget
                or edges + r.num_edges > self._edge_budget
            ):
                subs.append(cur)
                cur, nodes, edges = [], 0, 0
            cur.append(r)
            nodes += r.num_nodes
            edges += r.num_edges
        if cur:
            subs.append(cur)
        if len(subs) == 1:  # budget already below the floor: halve by count
            mid = len(wave) // 2
            subs = [wave[:mid], wave[mid:]]
        return subs

    def _run_wave(self, wave: list[GraphRequest]):
        from repro.core import connected_components
        from repro.trees import spanning_forest, tree_analytics

        if self.fault_plan is not None:
            self.fault_plan.check_wave(wave)

        if wave[0].kind == "sssp":  # family-pure by _next_wave
            return self._run_sssp_wave(wave)
        if wave[0].kind == "pagerank":
            return self._run_pagerank_wave(wave)

        stage = KINDS[max(_STAGE[r.kind] for r in wave)]
        node_off = np.cumsum([0] + [r.num_nodes for r in wave])
        n_union = int(node_off[-1])
        m_union = sum(r.num_edges for r in wave)
        node_cap = max(self.min_nodes, next_pow2(n_union))
        edge_cap = max(self.min_edges, next_pow2(max(m_union, 1)))
        if self.fault_plan is not None:
            self.fault_plan.check_bucket(node_cap)
        with trace.span(
            "serve.wave.pack", requests=len(wave), stage=stage,
            node_cap=node_cap, edge_cap=edge_cap,
        ):
            src = np.zeros((edge_cap,), np.int32)  # pad: inert self-loops
            dst = np.zeros((edge_cap,), np.int32)
            eo = 0
            for r, o in zip(wave, node_off):
                src[eo:eo + r.num_edges] = r.src + o
                dst[eo:eo + r.num_edges] = r.dst + o
                eo += r.num_edges

        bucket = (stage, node_cap, edge_cap)
        new_bucket = bucket not in self._buckets

        kw = dict(
            self.engine_kwargs, engine=self.engine, mesh=self.mesh,
            dedup=False,
        )
        if self.fault_plan is not None and self.fault_plan.wants_nonconverge(
            wave
        ):
            # Remove the round budget so the core engines' REAL
            # ConvergenceError sentinel fires for this wave.
            kw["max_rounds"] = 0
        # The engine span covers the batched device program AND the
        # readback (its own ``serve.wave.readback`` span) -- those reads
        # are the wave's existing host sync, so the span closes on an
        # already-synced boundary (no block_on needed).
        with trace.span(
            "serve.wave.engine", stage=stage, requests=len(wave),
            node_cap=node_cap, edge_cap=edge_cap, new_bucket=new_bucket,
        ) as esp:
            extras = None
            if stage == "cc":
                labels, rounds = connected_components(
                    src, dst, node_cap, **kw
                )
                edge_u = edge_v = None
            elif stage == "forest":
                forest = spanning_forest(src, dst, node_cap, **kw)
                labels, rounds = forest.labels, forest.rounds
                edge_u, edge_v = forest.edge_u, forest.edge_v
            else:
                ta = tree_analytics(
                    src, dst, node_cap,
                    rank_engine=self.rank_engine,
                    kernel_impl=self.kernel_impl,
                    num_splitters=self.num_splitters,
                    pad_edges_to=node_cap,
                    **kw,
                )
                labels, rounds = ta.forest.labels, ta.forest.rounds
                edge_u, edge_v = ta.forest.edge_u, ta.forest.edge_v
                extras = (
                    ta.parent, ta.depth, ta.subtree_size,
                    ta.computations.preorder, ta.computations.postorder,
                )
            with trace.span("serve.wave.readback", stage=stage):
                if stage == "cc":  # the forest stages read these already
                    trace.count("host_sync", 2)
                    labels, rounds = np.asarray(labels), int(rounds)
                if extras is not None:
                    trace.count("host_sync", len(extras))
                    extras = tuple(np.asarray(x) for x in extras)
            esp.tag(rounds=rounds)

        with trace.span("serve.wave.unpack", requests=len(wave)):
            self._unpack(wave, node_off, labels, edge_u, edge_v, extras)

        # Bucket accounting only for waves that ran to completion: a
        # wave that failed above (injected fault, OOM, engine error)
        # never instantiated the bucket's compiled programs.
        self._buckets.add(bucket)
        rec = WaveRecord(
            requests=len(wave), stage=stage,
            num_nodes=n_union, num_edges=m_union,
            node_cap=node_cap, edge_cap=edge_cap,
            new_bucket=new_bucket, rounds=rounds,
        )
        self.wave_records.append(rec)
        rec.publish(self.metrics)

    def _run_sssp_wave(self, wave: list[GraphRequest]):
        """The sssp-family wave: one batched multi-source
        ``shortest_paths`` call over the disjoint union. Every
        request's sources become rows of the packed distance array
        (offset-shifted), padded to a ``src_cap`` power-of-two row
        count; pad edges are +inf-weight self-loops (inert under
        relax-min, never parents) and pad source rows target a pad
        node when one exists (an isolated node: the row converges
        immediately). Disjoint union ⇒ request i's rows are its solo
        rows bit-exactly: no finite-weight path crosses an offset
        boundary, so other requests' columns stay +inf / -1 and are
        sliced away at unpack. ``fault_plan.check_wave`` already ran
        in ``_run_wave``."""
        from repro.core import shortest_paths

        stage = "sssp"
        node_off = np.cumsum([0] + [r.num_nodes for r in wave])
        n_union = int(node_off[-1])
        m_union = sum(r.num_edges for r in wave)
        node_cap = max(self.min_nodes, next_pow2(n_union))
        edge_cap = max(self.min_edges, next_pow2(max(m_union, 1)))
        row_off = np.cumsum([0] + [len(r.sources) for r in wave])
        src_cap = next_pow2(int(row_off[-1]))
        if self.fault_plan is not None:
            self.fault_plan.check_bucket(node_cap)
        with trace.span(
            "serve.wave.pack", requests=len(wave), stage=stage,
            node_cap=node_cap, edge_cap=edge_cap, src_cap=src_cap,
        ):
            src = np.zeros((edge_cap,), np.int32)  # pad: self-loops...
            dst = np.zeros((edge_cap,), np.int32)
            wts = np.full((edge_cap,), np.inf, np.float32)  # ...at +inf
            pad_src = n_union if n_union < node_cap else 0
            srcs = np.full((src_cap,), pad_src, np.int32)
            eo = 0
            for r, o, ro in zip(wave, node_off, row_off):
                src[eo:eo + r.num_edges] = r.src + o
                dst[eo:eo + r.num_edges] = r.dst + o
                wts[eo:eo + r.num_edges] = r.weights
                eo += r.num_edges
                srcs[ro:ro + len(r.sources)] = r.sources + o

        bucket = (stage, node_cap, edge_cap, src_cap)
        new_bucket = bucket not in self._buckets

        # "auto" resolves to "dense" for the same reason as CC serving:
        # the frontier ladder's data-dependent inner buckets would break
        # the wave's compile-count guarantee. A pinned "frontier" is
        # honoured (bit-exact; bounded ladder compiles per bucket).
        engine = "frontier" if self.engine == "frontier" else "dense"
        kw = dict(self.engine_kwargs)  # only min_bucket= survives submit
        if engine != "frontier":
            kw.pop("min_bucket", None)
        if self.fault_plan is not None and self.fault_plan.wants_nonconverge(
            wave
        ):
            kw["max_rounds"] = 0  # fire the REAL relax-bound sentinel
        with trace.span(
            "serve.wave.engine", stage=stage, requests=len(wave),
            node_cap=node_cap, edge_cap=edge_cap, src_cap=src_cap,
            new_bucket=new_bucket, engine=engine,
        ) as esp:
            dist, pred, rounds = shortest_paths(
                src, dst, wts, node_cap, sources=srcs, engine=engine, **kw
            )
            dist = np.asarray(dist)
            pred = np.asarray(pred)
            esp.tag(rounds=int(rounds))

        with trace.span("serve.wave.unpack", requests=len(wave)):
            for r, o, ro in zip(wave, node_off, row_off):
                hi = o + r.num_nodes
                p = pred[ro:ro + len(r.sources), o:hi]
                r.result = GraphResult(
                    dist=dist[ro:ro + len(r.sources), o:hi],
                    # unreachable stays -1; reachable parents shift back
                    pred=np.where(p >= 0, p - o, -1).astype(np.int32),
                    sources=r.sources.copy(),
                )
                r.done = True

        self._buckets.add(bucket)
        rec = WaveRecord(
            requests=len(wave), stage=stage,
            num_nodes=n_union, num_edges=m_union,
            node_cap=node_cap, edge_cap=edge_cap,
            new_bucket=new_bucket, rounds=int(rounds), src_cap=src_cap,
        )
        self.wave_records.append(rec)
        rec.publish(self.metrics)

    def _run_pagerank_wave(self, wave: list[GraphRequest]):
        """The pagerank-family wave: one dense fixed-iteration
        ``pagerank`` call over the disjoint union. Each request keeps
        its SOLO teleport vector in its node slice (``1/n_i`` uniform
        mass -- the same float64-literal rounding the solo default
        uses), pad nodes get teleport 0, and pad edges are
        weight-0.0 self-loops: they push zero mass and add zero
        degree, and ``x + 0.0f == x`` bitwise for the non-negative
        scores/degrees PageRank produces. Mass never crosses an
        offset boundary in a disjoint union and the packed edge-slot
        order restricted to one request is its solo order (forward
        arcs then backward arcs, pads between them contributing
        +0.0), so the deterministic scatter-add accumulates each
        node's mass in exactly its solo sequence: every unpacked
        ``scores`` slice is bit-identical to the solo dense run at
        ``pagerank_iters`` iterations (asserted in
        ``tests/test_serve_graph.py``). ``fault_plan.check_wave``
        already ran in ``_run_wave``."""
        from repro.core.pagerank import pagerank

        stage = "pagerank"
        node_off = np.cumsum([0] + [r.num_nodes for r in wave])
        n_union = int(node_off[-1])
        m_union = sum(r.num_edges for r in wave)
        node_cap = max(self.min_nodes, next_pow2(n_union))
        edge_cap = max(self.min_edges, next_pow2(max(m_union, 1)))
        if self.fault_plan is not None:
            self.fault_plan.check_bucket(node_cap)
        with trace.span(
            "serve.wave.pack", requests=len(wave), stage=stage,
            node_cap=node_cap, edge_cap=edge_cap,
        ):
            src = np.zeros((edge_cap,), np.int32)  # pad: self-loops...
            dst = np.zeros((edge_cap,), np.int32)
            wts = np.zeros((edge_cap,), np.float32)  # ...of weight 0
            tel = np.zeros((node_cap,), np.float32)
            eo = 0
            for r, o in zip(wave, node_off):
                src[eo:eo + r.num_edges] = r.src + o
                dst[eo:eo + r.num_edges] = r.dst + o
                wts[eo:eo + r.num_edges] = r.weights
                eo += r.num_edges
                tel[o:o + r.num_nodes] = np.full(
                    r.num_nodes, 1.0 / r.num_nodes, np.float32
                )

        bucket = (stage, node_cap, edge_cap)
        new_bucket = bucket not in self._buckets

        kw = {}
        if self.fault_plan is not None and self.fault_plan.wants_nonconverge(
            wave
        ):
            # Cap the iteration budget below the fixed count so the
            # dense engine's REAL ConvergenceError sentinel fires.
            kw["max_rounds"] = 0
        with trace.span(
            "serve.wave.engine", stage=stage, requests=len(wave),
            node_cap=node_cap, edge_cap=edge_cap, new_bucket=new_bucket,
            engine="dense",
        ) as esp:
            scores, iters = pagerank(
                src, dst, wts, node_cap,
                damping=self.damping, teleport=tel,
                num_iters=self.pagerank_iters, engine="dense", **kw,
            )
            scores = np.asarray(scores)
            esp.tag(rounds=int(iters))

        with trace.span("serve.wave.unpack", requests=len(wave)):
            for r, o in zip(wave, node_off):
                r.result = GraphResult(
                    scores=scores[o:o + r.num_nodes].copy()
                )
                r.done = True

        self._buckets.add(bucket)
        rec = WaveRecord(
            requests=len(wave), stage=stage,
            num_nodes=n_union, num_edges=m_union,
            node_cap=node_cap, edge_cap=edge_cap,
            new_bucket=new_bucket, rounds=int(iters),
        )
        self.wave_records.append(rec)
        rec.publish(self.metrics)

    def _unpack(self, wave, node_off, labels, edge_u, edge_v, extras):
        """Slice the packed union's outputs back to request-local ids."""
        from repro.core import num_components

        if extras is not None:
            parent, depth, size, pre, post = extras
        for r, o in zip(wave, node_off):
            hi = o + r.num_nodes
            lab = labels[o:hi] - o
            res = GraphResult(
                labels=lab.astype(np.int32),
                num_components=num_components(lab),
            )
            # fill only the fields the request's OWN kind asked for --
            # stage promotion must not leak wave-mate-dependent extras
            if edge_u is not None and _STAGE[r.kind] >= _STAGE["forest"]:
                # request i's forest edges are the hook slots of its own
                # node range, already in solo (hooked-tree id) order
                m = (edge_u >= o) & (edge_u < hi)
                res.edge_u = (edge_u[m] - o).astype(np.int32)
                res.edge_v = (edge_v[m] - o).astype(np.int32)
            if extras is not None and r.kind == "analytics":
                res.parent = (parent[o:hi] - o).astype(np.int32)
                res.depth = depth[o:hi]
                res.subtree_size = size[o:hi]
                res.preorder = pre[o:hi]
                res.postorder = post[o:hi]
            r.result = res
            r.done = True

    def run(self) -> list[GraphRequest]:
        """Process the whole queue; returns the requests that reached a
        terminal state during THIS call, in completion order:
        ``result`` populated (``done``) or quarantined (``failed`` with
        ``error`` set; only under injected/real faults -- see
        ``docs/serving.md``)."""
        return super().run()
