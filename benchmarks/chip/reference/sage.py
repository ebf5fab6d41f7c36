"""Plain reference of SpreadFGL rounds with a 2-layer GraphSAGE classifier.

Written from the paper (arXiv:2407.11085, Algorithm 1, Eq. 3 and 7-16) in
straightforward ``jax.numpy``; it imports nothing of the system under test
and takes none of its weights. It makes its own weights from the run's key,
with the same initialisers and key derivation the configuration states, and
reads only the client batch (the cell's input data) as host arrays.

One global round, for M clients grouped contiguously under N edge servers:

1. Local training (Algorithm 1 lines 8-9): ``local_rounds`` Adam steps
   (lr 0.01) on the summed per-client masked cross-entropy (Eq. 7) plus the
   Eq. 15 trace term 1e-4 * ||W_L||_F^2 on the last layer when N > 1.
   GraphSAGE layer (Eq. 3): h' = h W_self + mean_{u in N(v)} h_u W_nbr + b,
   ReLU between layers, padded slots masked to 0.
2. Every K rounds, the imputation round (lines 11-24), per edge server:
   softmax embeddings of its clients fused into one flat H (Eq. 9);
   negative mask e = [H > 1/c]; noise S ~ N(0, 1) fixed for the round; three
   outer passes of 5 autoencoder steps (Eq. 14) then 3 assessor steps
   (Eq. 13), Adam lr 1e-3; X_bar = f(S); per row, the top-k most similar
   slots of other clients among real local nodes (A_bar = H H^T); each client
   then writes its ``aug_max`` strongest links into its augmentation slots
   with the target's X_bar as features.
3. Aggregation: Eq. 16 over the ring adjacency with self loops (method
   ``SpreadFGL``), or gossip (``spreadfgl_gossip``): per-server FedAvg every
   round, and every ``gossip_every``-th round the ring average of each server
   with its two neighbours; or FedAvg over all clients at one server with no
   imputation round (``fedavg_fusion``, the Sec. IV-A baseline).
4. Evaluation: the mean client loss (as in step 1).

Precision: ``"stated"`` computes as the configuration states (f32 storage;
the aggregation's forward and the similarity gram at ``HIGHEST``; every other
matmul, the aggregation's backward included, at the default precision).
``"control"`` is one step below: ``HIGH`` for the former, bfloat16 operands
and results for the latter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_EPS = 1e-6
TRACE_REG = 1e-4
LR_CLASSIFIER = 0.01
LR_GENERATOR = 0.001
AE_HIDDEN = 16
ASSESSOR_HIDDEN = (128, 16)
AE_ITERS, ASSESSOR_ITERS, OUTER_ITERS = 5, 3, 3
GRAM_BLOCK = 1024
IMPUTING = ("SpreadFGL", "spreadfgl_gossip")


class Precision:
    """Where each matmul of the round computes, by the precision mode."""

    def __init__(self, mode: str):
        if mode not in ("stated", "control"):
            raise ValueError(f"unknown precision mode {mode!r}")
        self.mode = mode

    def dense(self, a, b, spec=None):
        """a @ b, or ``einsum(spec, a, b)``, at the dense layers' precision."""
        op = jnp.matmul if spec is None else functools.partial(jnp.einsum, spec)
        if self.mode == "stated":
            return op(a, b, precision=lax.Precision.DEFAULT)
        return op(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                  preferred_element_type=jnp.bfloat16).astype(jnp.float32)

    @property
    def exact(self):
        return (lax.Precision.HIGHEST if self.mode == "stated"
                else lax.Precision.HIGH)

    def aggregate(self, a_norm, h):
        """a_norm @ h: forward at the exact precision, backward as dense."""
        @jax.custom_vjp
        def agg(a, x):
            return jnp.matmul(a, x, precision=self.exact)

        def fwd(a, x):
            return agg(a, x), a

        def bwd(a, g):
            return jnp.zeros_like(a), self.dense(a.T, g)

        agg.defvjp(fwd, bwd)
        return agg(a_norm, h)


# -- weights from the key -----------------------------------------------------

def _glorot(key, shape):
    lim = jnp.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, minval=-lim, maxval=lim,
                              dtype=jnp.float32)


def _dense_layers(key, dims):
    return [{"w": _glorot(k, (dims[i], dims[i + 1])),
             "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i, k in enumerate(jax.random.split(key, len(dims) - 1))]


def init_weights(key, m, n, d, hidden, c):
    """Classifier (broadcast to the M clients), N autoencoders, N assessors."""
    k_cls, k_ae, k_as, k_run = jax.random.split(key, 4)
    layers = []
    dims = (d, hidden, c)
    for i, k in enumerate(jax.random.split(k_cls, len(dims) - 1)):
        k1, k2 = jax.random.split(k)
        layers.append({"w_self": _glorot(k1, (dims[i], dims[i + 1])),
                       "w_nbr": _glorot(k2, (dims[i], dims[i + 1])),
                       "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    cls = jax.tree.map(lambda p: jnp.broadcast_to(p, (m,) + p.shape),
                       {"layers": layers})

    def autoencoder(k):
        ks = jax.random.split(k, 4)
        return {"enc": [{"w": _glorot(ks[0], (c, AE_HIDDEN)), "b": jnp.zeros((AE_HIDDEN,))},
                        {"w": _glorot(ks[1], (AE_HIDDEN, d)), "b": jnp.zeros((d,))}],
                "dec": [{"w": _glorot(ks[2], (d, AE_HIDDEN)), "b": jnp.zeros((AE_HIDDEN,))},
                        {"w": _glorot(ks[3], (AE_HIDDEN, c)), "b": jnp.zeros((c,))}]}

    def assessor(k):
        return {"layers": _dense_layers(k, (c,) + ASSESSOR_HIDDEN + (1,))}

    servers = jnp.arange(n)
    ae = jax.vmap(lambda j: autoencoder(jax.random.fold_in(k_ae, j)))(servers)
    asr = jax.vmap(lambda j: assessor(jax.random.fold_in(k_as, j)))(servers)
    return cls, ae, asr, k_run


# -- Adam ----------------------------------------------------------------------

def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "mu": zeros,
            "nu": jax.tree.map(jnp.zeros_like, params)}


def adam_update(grads, opt, params, lr):
    b1, b2 = 0.9, 0.999
    step = opt["step"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    t = step.astype(jnp.float32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + 1e-8),
        params, mu, nu)
    return params, {"step": step, "mu": mu, "nu": nu}


# -- the classifier ----------------------------------------------------------

def sage_logits(params, x, adj, node_mask, prec):
    mask2d = node_mask[:, None] * node_mask[None, :]
    a = adj * mask2d
    a_norm = a / jnp.maximum(jnp.sum(a, axis=-1, keepdims=True), 1.0)
    h = x * node_mask[:, None]
    last = len(params["layers"]) - 1
    for li, layer in enumerate(params["layers"]):
        agg = prec.aggregate(a_norm, h)
        h = prec.dense(h, layer["w_self"]) + prec.dense(agg, layer["w_nbr"]) + layer["b"]
        if li < last:
            h = jax.nn.relu(h)
        h = h * node_mask[:, None]
    return h


def client_loss(params, x, adj, y, node_mask, train_mask, prec, spread):
    logits = sage_logits(params, x, adj, node_mask, prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(y, 0)[:, None], axis=-1)[:, 0]
    mask = train_mask * (y >= 0)
    loss = -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if spread:
        last = params["layers"][-1]
        loss = loss + TRACE_REG * (jnp.sum(last["w_self"] ** 2)
                                   + jnp.sum(last["w_nbr"] ** 2))
    return loss


# -- the generator round ------------------------------------------------------

def _mlp(layers, z, prec):
    for li, layer in enumerate(layers):
        z = prec.dense(z, layer["w"]) + layer["b"]
        if li < len(layers) - 1:
            z = jax.nn.relu(z)
    return z


def encode(ae, s, prec):
    return _mlp(ae["enc"], s, prec)


def decode(ae, x_bar, prec):
    return jax.nn.softmax(_mlp(ae["dec"], x_bar, prec), axis=-1)


def assess(asr, h, prec):
    return jax.nn.sigmoid(_mlp(asr["layers"], h, prec)[..., 0])


def _masked_mean(v, mask):
    return jnp.sum(v * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def ae_loss(ae, asr, s, h, e, fmask, prec):
    h_fake = decode(ae, encode(ae, s, prec), prec)
    adv = jnp.log1p(-assess(asr, h_fake * e, prec) + _EPS)
    neg = (h - h_fake) * (1.0 - e)
    return _masked_mean(adv + jnp.sum(neg * neg, axis=-1), fmask)


def as_loss(asr, h, h_fake, e, fmask, prec):
    per = (jnp.log1p(-assess(asr, h * e, prec) + _EPS)
           + jnp.log(assess(asr, h_fake * e, prec) + _EPS))
    return _masked_mean(per, fmask)


def train_generator(key, ae, ae_opt, asr, as_opt, h, fmask, c, prec):
    e = (h > 1.0 / c).astype(jnp.float32)
    _, ks = jax.random.split(key)
    s = jax.random.normal(ks, h.shape, dtype=jnp.float32)
    for _ in range(OUTER_ITERS):
        frozen_as = asr
        for _ in range(AE_ITERS):
            g = jax.grad(ae_loss)(ae, frozen_as, s, h, e, fmask, prec)
            ae, ae_opt = adam_update(g, ae_opt, ae, LR_GENERATOR)
        h_fake = decode(ae, encode(ae, s, prec), prec)
        for _ in range(ASSESSOR_ITERS):
            g = jax.grad(as_loss)(asr, h, h_fake, e, fmask, prec)
            asr, as_opt = adam_update(g, as_opt, asr, LR_GENERATOR)
    return ae, ae_opt, asr, as_opt, encode(ae, s, prec)


def cross_client_topk(h, fmask, cid, tmask, k, prec):
    """Per row, the k most similar target slots owned by another client."""
    n = h.shape[0]
    blocks = -(-n // GRAM_BLOCK)
    pad = blocks * GRAM_BLOCK - n
    rows = jnp.pad(h, ((0, pad), (0, 0))).reshape(blocks, GRAM_BLOCK, -1)
    rcid = jnp.pad(cid, (0, pad), constant_values=-1).reshape(blocks, GRAM_BLOCK)

    def block(args):
        r, rc = args
        gram = jnp.matmul(r, h.T, precision=prec.exact)
        ok = (rc[:, None] != cid[None, :]) & (tmask[None, :] > 0)
        return lax.top_k(jnp.where(ok, gram, -jnp.inf), k)

    scores, idx = lax.map(block, (rows, rcid))
    scores = scores.reshape(-1, k)[:n]
    idx = idx.reshape(-1, k)[:n]
    valid = (fmask[:, None] > 0) & jnp.isfinite(scores)
    return jnp.where(valid, scores, 0.0), jnp.where(valid, idx, -1)


def patch_client(x, adj, node_mask, scores, idx, x_bar, n_local, aug_max):
    """Write the client's aug_max strongest links into its augmentation slots."""
    n_pad, k = scores.shape
    src = jnp.repeat(jnp.arange(n_pad), k)
    tgt = idx.reshape(-1)
    ok = (tgt >= 0) & (src < n_local) & (node_mask[src] > 0)
    top_s, top_i = lax.top_k(jnp.where(ok, scores.reshape(-1), -jnp.inf), aug_max)
    chosen = jnp.isfinite(top_s).astype(jnp.float32)
    src, tgt = src[top_i], jnp.maximum(tgt[top_i], 0)
    aug = n_local + jnp.arange(aug_max)
    x = x.at[n_local:].set(0.0).at[aug].set(x_bar[tgt] * chosen[:, None])
    adj = adj.at[n_local:, :].set(0.0).at[:, n_local:].set(0.0)
    adj = adj.at[src, aug].set(chosen).at[aug, src].set(chosen)
    return x, adj, node_mask.at[aug].set(chosen)


# -- one round ---------------------------------------------------------------

class Reference:
    """The rounds of one cell, jitted per phase; shapes fixed by the batch."""

    def __init__(self, *, method, servers, local_rounds, imputation_interval,
                 top_k, gossip_every, participation, aug_max, num_classes,
                 hidden, precision):
        if participation != 1.0:
            raise NotImplementedError("the reference runs full participation only")
        if method not in IMPUTING + ("fedavg_fusion",):
            raise NotImplementedError(f"no reference for method {method!r}")
        self.method, self.n = method, servers
        self.local_rounds, self.k_imp = local_rounds, imputation_interval
        self.top_k, self.gossip_every = top_k, gossip_every
        self.aug_max, self.c, self.hidden = aug_max, num_classes, hidden
        self.prec = Precision(precision)
        self._local = jax.jit(self._local_impl)
        self._impute = jax.jit(self._impute_impl)
        self._aggregate = jax.jit(self._aggregate_impl, static_argnums=1)
        self._evaluate = jax.jit(self._evaluate_impl)

    # Local training: one Adam state per trainer, all clients in one sum.
    def _loss(self, params, b):
        per = jax.vmap(functools.partial(client_loss, prec=self.prec,
                                         spread=self.n > 1))(
            params, b["x"], b["adj"], b["y"], b["node_mask"], b["train_mask"])
        return jnp.sum(per)

    def _local_impl(self, params, opt, b):
        for _ in range(self.local_rounds):
            g = jax.grad(self._loss)(params, b)
            params, opt = adam_update(g, opt, params, LR_CLASSIFIER)
        return params, opt

    def _impute_impl(self, params, gen, b, key):
        m, n_pad = b["node_mask"].shape
        m_per = m // self.n
        n_local = n_pad - self.aug_max
        logits = jax.vmap(lambda p, x, a, nm: sage_logits(p, x, a, nm, self.prec))(
            params, b["x"], b["adj"], b["node_mask"])
        emb = jax.nn.softmax(logits, axis=-1)
        keys = jax.random.split(key, self.n + 1)
        cid = jnp.repeat(jnp.arange(m_per), n_pad)
        local = jnp.tile((jnp.arange(n_pad) < n_local).astype(jnp.float32), m_per)
        new_gen, all_scores, all_idx, all_xbar = [], [], [], []
        for j in range(self.n):
            sl = slice(j * m_per, (j + 1) * m_per)
            h = emb[sl].reshape(m_per * n_pad, self.c)
            fmask = b["node_mask"][sl].reshape(-1)
            take = lambda t: jax.tree.map(lambda v: v[j], t)
            ae, ae_opt, asr, as_opt, x_bar = train_generator(
                keys[j + 1], take(gen["ae"]), take(gen["ae_opt"]),
                take(gen["as"]), take(gen["as_opt"]), h, fmask, self.c, self.prec)
            scores, idx = cross_client_topk(h, fmask, cid, fmask * local,
                                            self.top_k, self.prec)
            new_gen.append({"ae": ae, "ae_opt": ae_opt, "as": asr, "as_opt": as_opt})
            all_scores.append(scores)
            all_idx.append(jnp.where(idx >= 0, idx + j * m_per * n_pad, -1))
            all_xbar.append(x_bar)
        gen = jax.tree.map(lambda *v: jnp.stack(v), *new_gen)
        scores = jnp.concatenate(all_scores).reshape(m, n_pad, -1)
        idx = jnp.concatenate(all_idx).reshape(m, n_pad, -1)
        x_bar = jnp.concatenate(all_xbar)
        x, adj, nm = jax.vmap(functools.partial(
            patch_client, x_bar=x_bar, n_local=n_local, aug_max=self.aug_max))(
            b["x"], b["adj"], b["node_mask"], scores, idx)
        return gen, dict(b, x=x, adj=adj, node_mask=nm), keys[0]

    def _aggregate_impl(self, params, exchange):
        """Eq. 16 (a matmul with the ring adjacency) or gossip (adds)."""
        n = self.n
        ring = np.zeros((n, n), np.float32)
        for j in range(n):
            ring[j, [(j - 1) % n, j, (j + 1) % n]] = 1.0

        def leaf(p):
            m_per = p.shape[0] // n
            grouped = p.reshape((n, m_per) + p.shape[1:])
            if self.method == "SpreadFGL":
                num = self.prec.dense(jnp.asarray(ring), jnp.sum(grouped, 1),
                                      "rj,r...->j...")
                server = num / (ring.sum(0) * m_per).reshape((n,) + (1,) * (p.ndim - 1))
            else:
                server = jnp.sum(grouped, 1) / m_per
                if exchange and n >= 3:
                    server = (server + jnp.roll(server, 1, 0)
                              + jnp.roll(server, -1, 0)) / 3.0
                elif exchange and n == 2:
                    server = jnp.broadcast_to(jnp.mean(server, 0), server.shape)
            return jnp.repeat(server, m_per, axis=0)
        return jax.tree.map(leaf, params)

    def _evaluate_impl(self, params, b):
        return self._loss(params, b) / b["x"].shape[0]

    def run(self, key, batch, rounds):
        """Rounds 0..rounds-1 from the key; returns what the check compares.

        ``{"init": weights, "moment": Adam first moments after round 0,
        "final": weights after the last round, "loss": [per round]}``,
        weights as {"cls", "ae", "as"} host trees.
        """
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        m, _, d = b["x"].shape
        cls, ae, asr, rkey = init_weights(key, m, self.n, d, self.hidden, self.c)
        gen = {"ae": ae, "ae_opt": jax.vmap(adam_init)(ae),
               "as": asr, "as_opt": jax.vmap(adam_init)(asr)}
        opt = adam_init(cls)
        out = {"init": jax.device_get({"cls": cls, "ae": ae, "as": asr}), "loss": []}
        for t in range(rounds):
            cls, opt = self._local(cls, opt, b)
            if self.method in IMPUTING and t % self.k_imp == 0:
                gen, b, rkey = self._impute(cls, gen, b, rkey)
            exchange = (self.method == "SpreadFGL"
                        or (t + 1) % self.gossip_every == 0)
            cls = self._aggregate(cls, bool(exchange))
            out["loss"].append(float(self._evaluate(cls, b)))
            if t == 0:
                out["moment"] = jax.device_get(
                    {"cls": opt["mu"], "ae": gen["ae_opt"]["mu"],
                     "as": gen["as_opt"]["mu"]})
        out["final"] = jax.device_get({"cls": cls, "ae": gen["ae"], "as": gen["as"]})
        return out

