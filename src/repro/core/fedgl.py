"""FGL training engine (Algorithm 1) with an explicit state lifecycle.

One engine covers every framework in the repo; the variation axes are
injected strategies (:mod:`repro.core.strategies`): a ``Topology`` maps
clients onto edge servers, an ``Aggregator`` combines client classifiers each
round, and an ``ImputationStrategy`` runs the every-K graph-fixing round.
``FedGL`` is star + FedAvg + the SpreadFGL generator; ``SpreadFGL`` is ring +
Eq. 16 + the generator; the Sec. IV-A baselines are other compositions (see
:mod:`repro.core.registry`).

Lifecycle::

    state = trainer.init(key, batch)        # fresh FGLState at round 0
    state, metrics = trainer.step(state)    # ONE global round of Algorithm 1
    state, history = trainer.fit(key, batch, rounds=30)   # thin step() loop
    state, history = trainer.fit(state=restored, rounds=10)  # true resume

``fit(state=...)`` continues at ``state.round`` — checkpoints written with
:mod:`repro.checkpoint.io` round-trip into an identical continuation (the
imputation schedule keys off the absolute round index). Per-round metrics
are accumulated as device arrays and fetched once at the end of ``fit`` —
no blocking host sync inside the loop.

Layout: client classifiers are stacked on a leading [M] axis; clients are
grouped contiguously per server so a ``[N, M_per]`` reshape recovers the edge
topology. All per-edge-server state (autoencoder, assessor, and their
optimizer states) is likewise stacked on a leading ``[N]`` axis — there are no
Python lists of per-server pytrees — and the whole imputation round is a
single ``jax.vmap`` over that axis. When an edge mesh is supplied
(``make_edge_mesh`` in ``launch/mesh.py``) the ``[N]`` axis is placed on a
JAX device mesh, and every per-client and per-server map (:meth:`FGLTrainer.
vmap`) runs under ``shard_map``: each device maps its own block of servers
and their clients, so a Pallas kernel inside the map sees one device's
block (Mosaic kernels cannot be partitioned by the compiler). Everything jits;
the outer edge-client communication loop is a Python loop (it mutates graph
structure on imputation rounds).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import assessor as assessor_lib
from repro.core import gnn, imputation, patcher, strategies
from repro.core import imputation as imputation_lib  # the ctor arg shadows it
from repro.core.types import ClientBatch, FGLConfig
from repro.optim.adam import Adam

PyTree = Any


class _ForwardingJit:
    """``jax.jit(fn)`` whose program leaves out every output that hands back
    one of its inputs unchanged; a call returns those inputs themselves.

    A TPU runtime allocates a buffer for each output of a program when it
    launches it, about 50 us each on a v5e host, and an imputation round
    hands back 24 of its 73 state leaves untouched (the classifiers, their
    optimizer state, most of the batch). Which outputs are forwarded is read
    from the traced jaxpr, once per argument structure and types; the
    program keeps ``fn``'s name, so it is the module ``jit_<name>``. The
    arguments ``donate_argnums`` names are donated to the program (an output
    takes over each buffer, with no allocation) and are never forwarded.
    """

    def __init__(self, fn, donate_argnums=()):
        self._fn = fn
        self._donate = tuple(donate_argnums)
        self._plans: Dict[Any, Tuple[Any, list, Any]] = {}

    def _plan(self, args):
        leaves, in_tree = jax.tree.flatten(args)
        sig = (in_tree, tuple(x.aval if isinstance(x, jax.Array) else jax.typeof(x)
                              for x in leaves))
        plan = self._plans.get(sig)
        if plan is None:
            traced = jax.jit(self._fn).trace(*args)
            invars = traced.jaxpr.jaxpr.invars
            assert len(invars) == len(leaves), (len(invars), len(leaves))
            first = np.cumsum([0] + [len(jax.tree.leaves(a)) for a in args])
            donated = {j for a in self._donate for j in range(first[a], first[a + 1])}
            fwd = [next((j for j, v in enumerate(invars)
                         if v is out and j not in donated), None)
                   for out in traced.jaxpr.jaxpr.outvars]
            made = [i for i, j in enumerate(fwd) if j is None]
            fn = self._fn

            @functools.wraps(fn)
            def program(*a):
                outs = jax.tree.leaves(fn(*a))
                return [outs[i] for i in made]
            plan = self._plans[sig] = (jax.jit(program, donate_argnums=self._donate), fwd,
                                       jax.tree.structure(traced.out_info))
        return leaves, plan

    def __call__(self, *args):
        leaves, (program, fwd, out_tree) = self._plan(args)
        made = iter(program(*args))
        return jax.tree.unflatten(
            out_tree, [next(made) if j is None else leaves[j] for j in fwd])

    def lower(self, *args):
        """The lowering of the program a call with ``args`` runs."""
        return self._plan(args)[1][0].lower(*args)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FGLState:
    """The full Algorithm 1 state, threaded through ``step()`` as one pytree.

    Registered dataclass so the whole state jits, checkpoints, and shards as
    a single tree — the imputation round takes and returns ``FGLState``
    directly (no positional tuples).
    """

    params: PyTree        # [M, ...] stacked client classifiers
    opt_state: Any
    ae_params: PyTree     # [N, ...] stacked per-server autoencoders
    ae_opt: Any           # [N, ...] stacked optimizer state
    as_params: PyTree     # [N, ...] stacked per-server assessors
    as_opt: Any
    batch: ClientBatch
    key: jax.Array
    round: int = 0


def _cross_entropy(logits: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Eq. (7): masked CE; logits [n, c], y [n] with -1 on unlabeled."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    safe_y = jnp.maximum(y, 0)
    picked = jnp.take_along_axis(logp, safe_y[:, None], axis=-1)[:, 0]
    mask = mask * (y >= 0)
    return -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _trace_reg(params: PyTree) -> jnp.ndarray:
    """Eq. (15): Tr(W_L W_Lᵀ) = ||W_L||_F² on the last GNN layer's weights."""
    last = params["layers"][-1]
    return sum(jnp.sum(jnp.square(w)) for k, w in last.items() if k != "b")


class FGLTrainer:
    """Drives Algorithm 1 for a fixed client batch, one strategy per axis."""

    def __init__(self, cfg: FGLConfig, batch: ClientBatch,
                 *, topology: Optional[strategies.Topology] = None,
                 aggregator: Optional[strategies.Aggregator] = None,
                 imputation: Optional[strategies.ImputationStrategy] = None,
                 use_negative_sampling: bool = True, use_assessor: bool = True,
                 edge_mesh=None):
        if cfg.kernel_impl not in imputation_lib.KERNEL_IMPLS:
            raise ValueError(f"unknown kernel_impl {cfg.kernel_impl!r}; "
                             f"expected one of {imputation_lib.KERNEL_IMPLS}")
        if cfg.gnn_kind == "gat" and cfg.kernel_impl != "reference":
            raise ValueError(f"gnn_kind 'gat' has no Pallas kernel; "
                             f"kernel_impl {cfg.kernel_impl!r} would not run")
        if not 0.0 < cfg.participation <= 1.0:
            raise ValueError(f"participation must be in (0, 1], "
                             f"got {cfg.participation}")
        self.m = batch.num_clients
        self.topology = topology if topology is not None else strategies.StarTopology()
        layout = self.topology.build(self.m)
        self.n_servers = layout.num_servers
        self.m_per = layout.clients_per_server
        expected = np.repeat(np.arange(self.n_servers), self.m_per)
        if not np.array_equal(np.asarray(layout.server_of_client), expected):
            raise ValueError("clients must be grouped contiguously per server")
        self.cfg = cfg = dataclasses.replace(
            cfg, num_edge_servers=self.n_servers, clients_per_server=self.m_per)
        self.is_spread = self.n_servers > 1
        self.aggregator = aggregator if aggregator is not None else (
            strategies.NeighborAggregator() if self.is_spread
            else strategies.FedAvgAggregator())
        self.imputation = (imputation if imputation is not None
                           else strategies.SpreadImputation())

        self.num_classes = batch.num_classes
        self.adj_servers = jnp.asarray(layout.adjacency, jnp.float32)
        self.feature_dim = batch.x.shape[-1]
        self.kernel_impl = self.cfg.kernel_impl
        self.n_local = batch.n_local_max
        self.use_ns = use_negative_sampling
        self.use_assessor = use_assessor
        # The participation mask's own key stream (see `_schedule`).
        self._part_key = jax.random.fold_in(jax.random.key(cfg.seed), 0x9A57)
        self.opt = Adam(lr=cfg.lr_classifier)
        self.gen_opt = Adam(lr=cfg.lr_generator)
        self.edge_mesh = edge_mesh
        if edge_mesh is not None and self.n_servers % edge_mesh.size:
            raise ValueError(f"N={self.n_servers} servers must divide across the "
                             f"{edge_mesh.size}-device edge mesh")
        # The classifiers and their optimizer state are donated: each output
        # takes over an input's buffer, so a launch allocates none.
        self._local_fn = jax.jit(self._local_rounds, donate_argnums=(0, 1))
        # The schedule's flag is a STATIC arg: at most 2 compiled variants
        # (exchange/skip, flush/skip), and skip rounds lower to no
        # cross-server collectives.
        self._agg_fn = jax.jit(self._aggregate, static_argnames=("flag",))
        # step()'s own call: the classifiers (argument 0) are donated
        self._agg_step_fn = jax.jit(self._aggregate, static_argnames=("flag",),
                                    donate_argnums=(0,))
        self._impute_fn = _ForwardingJit(self._impute)
        # step()'s own call: the generator state and the batch's cache
        # (arguments 1 and 2) are donated
        self._impute_step_fn = _ForwardingJit(self._impute, donate_argnums=(1, 2))
        self._eval_fn = jax.jit(self._evaluate)
        self._propagate_fn = jax.jit(self._propagate)
        self._no_links = jnp.zeros((), jnp.int32)  # `links` of a round without imputation

    # -- initialization ------------------------------------------------------

    def init(self, key: jax.Array, batch: ClientBatch) -> FGLState:
        """Algorithm 1 lines 1-5: a fresh ``FGLState`` at round 0, its batch
        carrying the classifier's cache (``ClientBatch.prop``)."""
        cfg = self.cfg
        dims = [self.feature_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1) + [self.num_classes]
        k_cls, k_ae, k_as, k_run = jax.random.split(key, 4)
        # Algorithm 1 line 3: all clients start from the server weights W_j.
        base = gnn.init_classifier(k_cls, cfg.gnn_kind, dims)
        params = jax.tree.map(lambda p: jnp.broadcast_to(p, (self.m,) + p.shape).copy(), base)
        ae_params = imputation.init_stacked_autoencoder(
            k_ae, self.n_servers, self.num_classes, self.feature_dim, cfg.ae_hidden)
        as_params = assessor_lib.init_stacked_assessor(
            k_as, self.n_servers, self.num_classes, cfg.assessor_hidden)
        ae_opt = jax.vmap(self.gen_opt.init)(ae_params)
        as_opt = jax.vmap(self.gen_opt.init)(as_params)
        ae_params, ae_opt, as_params, as_opt = self._shard_edge(
            (ae_params, ae_opt, as_params, as_opt))
        batch = jax.tree.map(jnp.asarray, batch)
        batch = batch.replace(prop=self._propagate_fn(batch))
        return FGLState(params=params, opt_state=self.opt.init(params),
                        ae_params=ae_params, ae_opt=ae_opt,
                        as_params=as_params, as_opt=as_opt,
                        batch=batch, key=k_run)

    def vmap(self, fn, in_axes=0):
        """``jax.vmap`` over a leading client or server axis.

        On an edge mesh of more than one device the map runs under
        ``shard_map``: each device maps its contiguous block of the axis
        (clients are grouped per server, so client and server blocks line
        up). ``in_axes`` entries are 0 (mapped, split across the mesh) or
        None (broadcast, replicated).
        """
        mapped = jax.vmap(fn, in_axes=in_axes)
        mesh = self.edge_mesh
        if mesh is None or mesh.size == 1:
            return mapped
        from jax.sharding import PartitionSpec as P
        block = P(mesh.axis_names[0])

        def call(*args):
            axes = in_axes if isinstance(in_axes, tuple) else (in_axes,) * len(args)
            specs = tuple(P() if a is None else block for a in axes)
            return jax.shard_map(mapped, mesh=mesh, in_specs=specs,
                                 out_specs=block, check_vma=False)(*args)
        return call

    def _shard_edge(self, tree: PyTree) -> PyTree:
        """Place the leading [N] server axis of stacked state on the edge mesh."""
        if self.edge_mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec
        spec = NamedSharding(self.edge_mesh,
                             PartitionSpec(self.edge_mesh.axis_names[0]))
        return jax.tree.map(lambda x: jax.device_put(x, spec), tree)

    # -- the classifier's forward ---------------------------------------------

    def _propagate(self, batch: ClientBatch) -> PyTree:
        """``gnn.propagate`` of every client: the classifier's work on the
        batch alone, which the batch caches as ``prop`` (scope
        ``propagate``). ``init`` and ``_impute`` fill the cache with it."""
        def one(x, adj, node_mask):
            return gnn.propagate(self.cfg.gnn_kind, x, adj, node_mask,
                                 impl=self.kernel_impl)
        with jax.named_scope("propagate"):
            return self.vmap(one)(batch.x, batch.adj, batch.node_mask)

    def _forward(self, params, x, adj, node_mask, prop):
        """One client's logits, from the batch's cache where it has one."""
        return gnn.apply_classifier(params, self.cfg.gnn_kind, x, adj, node_mask,
                                    impl=self.kernel_impl, prop=prop)

    # -- local training (Algorithm 1 lines 8-9) ------------------------------

    def _client_loss(self, params_m: PyTree, batch: ClientBatch) -> jnp.ndarray:
        def one(params, x, adj, y, node_mask, train_mask, prop):
            logits = self._forward(params, x, adj, node_mask, prop)
            loss = _cross_entropy(logits, y, train_mask)
            if self.is_spread and self.cfg.trace_reg > 0:
                loss = loss + self.cfg.trace_reg * _trace_reg(params)
            return loss
        losses = self.vmap(one)(params_m, batch.x, batch.adj, batch.y,
                                batch.node_mask, batch.train_mask, batch.prop)
        return jnp.sum(losses)  # sum => per-client grads stay independent

    def _local_rounds(self, params, opt_state, batch: ClientBatch):
        def step(carry, _):
            params, opt_state = carry
            grads = jax.grad(self._client_loss)(params, batch)
            params, opt_state = self.opt.update(grads, opt_state, params)
            return (params, opt_state), ()
        with jax.named_scope("local_train"):
            (params, opt_state), _ = jax.lax.scan(
                step, (params, opt_state), None, length=self.cfg.local_rounds)
        return params, opt_state

    # -- aggregation (strategy) ----------------------------------------------

    def _aggregate(self, params, *, flag=0, weights=None):
        """The Aggregator's call, under the ``aggregate`` scope whatever the
        strategy (FedAvg, Eq. 16, gossip, async)."""
        with jax.named_scope("aggregate"):
            return self.aggregator.aggregate(
                params, adj=self.adj_servers, num_servers=self.n_servers,
                m_per=self.m_per, flag=flag, weights=weights)

    def _schedule(self, t: int) -> Tuple[int, Optional[jnp.ndarray]]:
        """Round ``t``'s aggregation decision: ``(flag, weights)``.

        The aggregator's own schedule, with the [M] 0/1 participation mask
        of ``cfg.participation < 1`` multiplied into its weights: a client
        sampled out contributes zero weight even if its (stale) update sits
        in an async buffer. The mask draws from its OWN key stream, cfg.seed
        folded with a salt and then with t, never from the training key in
        ``FGLState``: ρ < 1 perturbs no other draw, and the mask is a pure
        function of (seed, t), like the aggregator's schedule, so a state
        restored mid-run resumes both exactly. Its shape is a static [M]
        every round (exactly ceil(ρ·M) participants), so the jitted
        aggregation compiles one weighted variant. At ρ = 1 with a
        synchronous aggregator the weights are None: no array is built or
        transferred.
        """
        flag, weights = self.aggregator.schedule(t, self.m)
        if self.cfg.participation < 1.0:
            key = jax.random.fold_in(self._part_key, t)
            mask = strategies.participation_mask(key, self.m, self.cfg.participation)
            weights = mask if weights is None else weights * mask
        return flag, weights

    def aggregate(self, params: PyTree, *, round: int = 0) -> PyTree:
        """Apply this trainer's Aggregator to stacked client classifiers,
        with round ``round``'s schedule (``_schedule``)."""
        flag, weights = self._schedule(int(round))
        return self._agg_fn(params, flag=flag, weights=weights)

    # -- imputation helpers shared by the strategies --------------------------

    def _embeddings(self, params, batch: ClientBatch) -> jnp.ndarray:
        def one(p, x, adj, mask, prop):
            return jax.nn.softmax(self._forward(p, x, adj, mask, prop), axis=-1)
        return self.vmap(one)(params, batch.x, batch.adj, batch.node_mask, batch.prop)

    def _train_generator(self, key, ae, ae_opt, asr, as_opt, h_real, flat_mask):
        """Alternating AE / assessor training (Algorithm 1 lines 16-23).

        Each of the ``ae_outer_iters`` passes trains the autoencoder against
        the assessor as it stands at the start of the pass, then the assessor
        against that pass's autoencoder. Both counterparts travel in the
        scans' carries, never in a closure: a ``lax.scan`` body is traced
        once and its trace reused, so a name rebound between scans would
        hand every pass the first pass's counterpart.

        The noise matrix S is sampled ONCE per imputation round (the only
        randomness here) and held fixed across AE/assessor iterations, so
        that row v of S is bound to node v: the masked reconstruction term of
        Eq. (14) then makes h(f(S))_v track h_v and the encoder output
        X̅_v = f(S)_v is a node-specific imputed feature (Sec. III-C: "X̅ =
        f(S) indicates the potential features"). The per-iteration scans are
        deliberately keyless — S is NOT resampled per iteration.
        Returns (ae, ae_opt, asr, as_opt, s_noise).
        """
        cfg = self.cfg
        theta = cfg.theta(self.num_classes)
        n = h_real.shape[0]
        e = (assessor_lib.negative_mask(h_real, theta) if self.use_ns
             else jnp.ones_like(h_real))
        _, ks = jax.random.split(key)
        s_noise = imputation.sample_noise(ks, n, self.num_classes)

        def ae_loss(ae, asr):
            if self.use_assessor:
                return assessor_lib.autoencoder_loss(ae, asr, s_noise, h_real, e,
                                                     flat_mask)
            # w/o assessor: plain masked reconstruction of H (Fig. 7 ablation).
            _, h_fake = imputation.reconstruct(ae, s_noise)
            diff = h_real - h_fake
            return jnp.sum(jnp.sum(diff * diff, -1) * flat_mask) / jnp.maximum(
                jnp.sum(flat_mask), 1.0)

        def as_loss(asr, h_fake):
            if self.use_ns:
                return assessor_lib.assessor_loss(asr, h_real, h_fake, e, flat_mask)
            return assessor_lib.assessor_loss_plain(asr, h_real, h_fake, flat_mask)

        def ae_step(carry, _):
            ae, ae_opt, asr = carry                 # asr: frozen this pass
            grads = jax.grad(ae_loss)(ae, asr)
            ae, ae_opt = self.gen_opt.update(grads, ae_opt, ae)
            return (ae, ae_opt, asr), ()

        def as_step(carry, _):
            asr, as_opt, h_fake = carry             # h_fake: this pass's AE
            grads = jax.grad(as_loss)(asr, h_fake)
            asr, as_opt = self.gen_opt.update(grads, as_opt, asr)
            return (asr, as_opt, h_fake), ()

        def outer_pass(carry, _):
            ae, ae_opt, asr, as_opt = carry
            (ae, ae_opt, _), _ = jax.lax.scan(ae_step, (ae, ae_opt, asr), None,
                                              length=cfg.ae_iters)
            if self.use_assessor:
                _, h_fake = imputation.reconstruct(ae, s_noise)
                (asr, as_opt, _), _ = jax.lax.scan(
                    as_step, (asr, as_opt, h_fake), None, length=cfg.assessor_iters)
            return (ae, ae_opt, asr, as_opt), ()

        (ae, ae_opt, asr, as_opt), _ = jax.lax.scan(
            outer_pass, (ae, ae_opt, asr, as_opt), None, length=cfg.ae_outer_iters)
        return ae, ae_opt, asr, as_opt, s_noise

    def _server_round_gen(self, key_j, ae, aeo, asr, aso, emb_j, mask_j):
        """The generator half of one server's imputation round.

        Fusion + adversarial AE/assessor training + X̅ = f(S); everything
        EXCEPT the similarity top-k, so the candidate-sharded path
        (``SpreadImputation.sim_mesh``) can vmap this part over the [N]
        server axis and run ONE batched ring top-k outside the vmap —
        shard_map-over-vmap is the fragile composition, vmap-then-shard_map
        is not. Returns the trained state, X̅, and the similarity search's
        inputs (``imputation.search_inputs``: fused h, flat mask, client ids,
        target mask) so the caller searches the exact same fused embeddings.
        """
        with jax.named_scope("generator"):
            search = imputation.search_inputs(emb_j, mask_j, self.n_local)
            ae, aeo, asr, aso, s_noise = self._train_generator(
                key_j, ae, aeo, asr, aso, search[0], search[1])
            x_bar = imputation.encode(ae, s_noise)          # X̅ = f(S), same S
        return ae, aeo, asr, aso, x_bar, search

    def _server_round(self, key_j, ae, aeo, asr, aso, emb_j, mask_j):
        """One edge server's imputation work on its [M_per, n_pad, c] slice."""
        ae, aeo, asr, aso, x_bar, (h, fmask, cid, tmask) = (
            self._server_round_gen(key_j, ae, aeo, asr, aso, emb_j, mask_j))
        with jax.named_scope("sim_topk"):
            scores, idx = imputation.similarity_topk(
                h, fmask, cid, self.cfg.top_k_links,
                kernel_impl=self.kernel_impl, target_mask=tmask)
        return ae, aeo, asr, aso, scores, idx, x_bar

    def _impute(self, state: FGLState, gen=None, prop=None
                ) -> Tuple[FGLState, jnp.ndarray]:
        """The strategy's imputation round, and the number of imputed links
        the patcher wrote into the client graphs (``patcher.link_count``).
        The patched batch leaves with its cache ``prop`` filled anew.

        ``gen``, where given, is the generator state ``(ae_params, ae_opt,
        as_params, as_opt)`` passed apart from ``state`` (whose own are then
        None), and ``prop`` the batch's cache passed apart from it, so that a
        call can donate them."""
        if gen is not None:
            state = dataclasses.replace(state, ae_params=gen[0], ae_opt=gen[1],
                                        as_params=gen[2], as_opt=gen[3])
        if prop is not None:
            state = dataclasses.replace(state, batch=state.batch.replace(prop=prop))
        state = self.imputation.impute(self, state)
        batch = state.batch.replace(prop=self._propagate(state.batch))
        return dataclasses.replace(state, batch=batch), patcher.link_count(batch)

    def _imputation_round_reference(self, state: FGLState) -> FGLState:
        """Sequential oracle of the vmapped generator round (tests/benchmarks).

        Only meaningful when this trainer's imputation strategy exposes a
        reference implementation (``SpreadImputation`` does).
        """
        return self.imputation.impute_reference(self, state)

    # -- evaluation ------------------------------------------------------------

    def _evaluate(self, params, batch: ClientBatch):
        """One compiled call per round: (mean client loss, accuracy, macro-F1)."""
        def one(p, x, adj, y, node_mask, test_mask, prop):
            logits = self._forward(p, x, adj, node_mask, prop)
            pred = jnp.argmax(logits, axis=-1)
            mask = test_mask * (y >= 0)
            correct = jnp.sum((pred == y) * mask)
            # Macro-F1 pieces per class.
            c = self.num_classes
            onehot_p = jax.nn.one_hot(pred, c) * mask[:, None]
            onehot_y = jax.nn.one_hot(jnp.maximum(y, 0), c) * mask[:, None]
            tp = jnp.sum(onehot_p * onehot_y, axis=0)
            fp = jnp.sum(onehot_p * (1 - onehot_y), axis=0)
            fn = jnp.sum((1 - onehot_p) * onehot_y, axis=0)
            return correct, jnp.sum(mask), tp, fp, fn
        with jax.named_scope("evaluate"):
            correct, total, tp, fp, fn = self.vmap(one)(
                params, batch.x, batch.adj, batch.y, batch.node_mask, batch.test_mask,
                batch.prop)
            acc = jnp.sum(correct) / jnp.maximum(jnp.sum(total), 1.0)
            tp, fp, fn = jnp.sum(tp, 0), jnp.sum(fp, 0), jnp.sum(fn, 0)
            precision = tp / jnp.maximum(tp + fp, 1e-9)
            recall = tp / jnp.maximum(tp + fn, 1e-9)
            f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-9)
            seen = (tp + fn) > 0
            macro_f1 = jnp.sum(jnp.where(seen, f1, 0.0)) / jnp.maximum(jnp.sum(seen), 1.0)
            loss = self._client_loss(params, batch) / self.m
            return loss, acc, macro_f1

    def evaluate(self, state: FGLState) -> Dict[str, jnp.ndarray]:
        """Metrics of the current state (device arrays, no host sync)."""
        loss, acc, f1 = self._eval_fn(state.params, state.batch)
        return {"loss": loss, "acc": acc, "f1": f1}

    # -- outer loop (Algorithm 1) ----------------------------------------------

    def step(self, state: FGLState) -> Tuple[FGLState, Dict[str, Any]]:
        """One global round of Algorithm 1 (lines 6-26).

        Local training, the strategy's imputation round when the absolute
        round index hits the every-K schedule, aggregation, then evaluation.
        Returns a new state at ``round + 1`` and metrics as device arrays
        (``{"round", "loss", "acc", "f1", "links"}``; ``links`` counts the
        imputed links written this round, 0 on a round without imputation)
        — callers decide when to sync. The caller's state object is never
        mutated, but its classifiers and their optimizer state (``params``,
        ``opt_state``) are donated to the local and aggregation programs
        every round, and on an imputation round its generator state
        (``ae_params``, ``ae_opt``, ``as_params``, ``as_opt``) and its batch's
        cache (``batch.prop``) to the imputation program: those arrays of the
        old state are deleted. Read what you keep of a state before stepping
        it (``fit`` rebinds ``state`` each round).

        Under ``jax.profiler`` the round is a ``fgl.round`` step span with
        one child span per dispatch (``fgl.local``, ``fgl.impute``,
        ``fgl.aggregate``, ``fgl.evaluate``) and ``fgl.schedule`` around the
        host's aggregation schedule, each carrying ``round=t``
        (``docs/TRACING.md``). With no profiler active a span costs under a
        microsecond.
        """
        t = int(state.round)
        span = functools.partial(jax.profiler.TraceAnnotation, round=t)
        with jax.profiler.StepTraceAnnotation("fgl.round", step_num=t, round=t):
            state = dataclasses.replace(state)   # never mutate the caller's state
            links = self._no_links
            with span("fgl.local"):
                state.params, state.opt_state = self._local_fn(
                    state.params, state.opt_state, state.batch)
            if self.imputation.active and (t % self.cfg.imputation_interval == 0):
                with span("fgl.impute"):
                    gen = (state.ae_params, state.ae_opt, state.as_params, state.as_opt)
                    state, links = self._impute_step_fn(
                        dataclasses.replace(state, ae_params=None, ae_opt=None,
                                            as_params=None, as_opt=None,
                                            batch=state.batch.replace(prop=None)),
                        gen, state.batch.prop)
            with span("fgl.schedule"):  # a pure function of the absolute round
                flag, weights = self._schedule(t)
            with span("fgl.aggregate"):
                state.params = self._agg_step_fn(state.params, flag=flag,
                                                 weights=weights)
            with span("fgl.evaluate"):
                loss, acc, f1 = self._eval_fn(state.params, state.batch)
            state.round = t + 1
        return state, {"round": t, "loss": loss, "acc": acc, "f1": f1,
                       "links": links}

    def fit(self, key: Optional[jax.Array] = None,
            batch: Optional[ClientBatch] = None, *,
            state: Optional[FGLState] = None, rounds: Optional[int] = None
            ) -> Tuple[FGLState, Dict[str, list]]:
        """Run ``rounds`` global rounds (default ``cfg.global_rounds``).

        Either pass ``(key, batch)`` for a fresh run, or ``state=`` (e.g. a
        checkpoint restored via :func:`repro.checkpoint.io.restore`) to
        resume — the loop continues at ``state.round`` with the imputation
        schedule intact. Metrics stay on device for the whole loop and are
        fetched with a single transfer at the end.
        """
        if state is None:
            if key is None or batch is None:
                raise ValueError("fit() needs (key, batch) for a fresh run "
                                 "or state= to resume")
            state = self.init(key, batch)
        else:
            if key is not None or batch is not None:
                raise ValueError("fit(state=...) resumes from the state's own "
                                 "key/batch; do not also pass key or batch")
            state = dataclasses.replace(state, round=int(state.round))
            # A restored checkpoint holds host arrays: put the stacked [N]
            # generator state back on the edge mesh before the vmapped round.
            (state.ae_params, state.ae_opt, state.as_params,
             state.as_opt) = self._shard_edge(
                (state.ae_params, state.ae_opt, state.as_params, state.as_opt))
        rounds = rounds if rounds is not None else self.cfg.global_rounds
        metrics = []
        for _ in range(rounds):
            state, m = self.step(state)
            metrics.append(m)
        metrics = jax.device_get(metrics)    # ONE host sync for the whole run
        history: Dict[str, list] = {
            "round": [int(m["round"]) for m in metrics],
            "loss": [float(m["loss"]) for m in metrics],
            "acc": [float(m["acc"]) for m in metrics],
            "f1": [float(m["f1"]) for m in metrics],
            "links": [int(m["links"]) for m in metrics],
        }
        return state, history
