"""Plain reference of FedDUMAP rounds, and the readings that decide a
training cell's ``correct``.

The round follows the paper's formulas as the program states them
(FedDU, Formulas 4-7; FedDUM, Formulas 8, 11, 12 in the descent-consistent
sign; FedAP's mask mode), the round's arithmetic in float32 and the
model's matmuls in the precision the configuration states, one client
after the other and one step after the other.  Parameters are stored in
the configuration's dtype at the points where the program stores them:
after each local and server step, after aggregation, after the FedDU
proposal and after the server momentum step.  Momentum buffers
are float32, as the program keeps them.

Each round's batches are drawn from the same key chain as the program's
device-side sampler (one split per round; selection without replacement,
epochs of permutations per client and for the server pool), copied here
from ``repro.core.engine.sample_round_batches`` with the non-IID degrees
of ``repro.core.niid``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS_KL = 1e-12


# ---------------------------------------------------------------------------
# the round's batches, from the key chain
# ---------------------------------------------------------------------------

def _epoch_indices(key, n, count):
    reps = -(-count // n)
    perms = jax.vmap(lambda k: jax.random.permutation(k, n))(
        jax.random.split(key, reps))
    return perms.reshape(-1)[:count]


def _kl(p, q):
    ratio = jnp.log(jnp.clip(p, EPS_KL, None)) - jnp.log(jnp.clip(q, EPS_KL,
                                                                  None))
    return jnp.sum(jnp.where(p > 0, p * ratio, 0.0), axis=-1)


def non_iid_degree(p, q):
    """Jensen-Shannon divergence (Formula 2)."""
    m = 0.5 * (p + q)
    return 0.5 * _kl(p, m) + 0.5 * _kl(q, m)


def mixture(dists, sizes):
    w = sizes / jnp.clip(jnp.sum(sizes), 1.0, None)
    return jnp.einsum("k,kc->c", w, dists)


@functools.partial(jax.jit, static_argnames=("clients", "batch", "steps",
                                             "server_batch", "tau"))
def draw_round(key, data, *, clients, batch, steps, server_batch, tau):
    k_sel, k_cl, k_srv = jax.random.split(key, 3)
    n_clients, n_k = data["client_y"].shape[:2]
    n0 = data["server_y"].shape[0]
    sel = jax.random.choice(k_sel, n_clients, (clients,), replace=False)
    idx = jax.vmap(lambda k: _epoch_indices(k, n_k, steps * batch))(
        jax.random.split(k_cl, clients))
    cx = jax.vmap(lambda x, i: x[i])(data["client_x"][sel], idx)
    cy = jax.vmap(lambda y, i: y[i])(data["client_y"][sel], idx)
    sidx = _epoch_indices(k_srv, n0, tau * server_batch)
    p_bar = mixture(data["client_dists"], data["sizes"])
    return {
        "cx": cx.reshape(clients, steps, batch, *cx.shape[2:]),
        "cy": cy.reshape(clients, steps, batch, *cy.shape[2:]),
        "sx": data["server_x"][sidx].reshape(tau, server_batch,
                                             *data["server_x"].shape[1:]),
        "sy": data["server_y"][sidx].reshape(tau, server_batch,
                                             *data["server_y"].shape[1:]),
        "sizes": data["sizes"][sel],
        "d_round": non_iid_degree(
            mixture(data["client_dists"][sel], data["sizes"][sel]), p_bar),
        "d_server": non_iid_degree(data["server_dist"], p_bar),
        "n0": jnp.asarray(n0, jnp.float32),
    }


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

# Clients train side by side (vmapped) when all of them together hold
# less than this in float32 parameters; otherwise one after the other.
VMAP_CLIENT_BYTES = 2 ** 30


class Reference:
    """FedDUMAP rounds of one configuration's plain model.

    ``loss_and_acc(params, x, y, fmask, precision)`` and
    ``mask_params(tree, fmask)`` come from the configuration's file (no
    ``fmask`` and no ``mask_params`` where the mix does not prune);
    ``param_dtype`` is the configuration's parameter dtype; ``hp`` is the
    training mix.  ``half_batch=True`` plants a fault: every step takes
    the mean over the first half of its batch only.
    """

    def __init__(self, loss_and_acc, mask_params, fmask, hp: dict, *,
                 param_dtype, precision: str = "f32",
                 half_batch: bool = False):
        self.hp = hp
        self.fmask = None if fmask is None else jnp.asarray(fmask)
        self.mask = ((lambda t: t) if fmask is None
                     else (lambda t: mask_params(t, self.fmask)))
        # ``reduce_precision`` rounds where the compiler keeps it; a cast to
        # ``param_dtype`` and back inside a jitted step may be fused away
        # (XLA's excess precision), which leaves the parameters unrounded
        bits = jnp.finfo(param_dtype)
        self.store = lambda tree: jax.tree.map(
            lambda x: jax.lax.reduce_precision(
                x, exponent_bits=bits.nexp, mantissa_bits=bits.nmant), tree)
        cut = (lambda a: a[:a.shape[0] // 2]) if half_batch else (lambda a: a)

        def la(p, x, y):
            return loss_and_acc(p, cut(x), cut(y), self.fmask, precision)

        vg = jax.value_and_grad(la, has_aux=True)
        beta = hp["feddum"]["beta_local"]

        def local_train(p, xs, ys, lr):
            """One client's local steps (damped SGDM, momentum restarted)."""
            def body(carry, xy):
                p, m = carry
                _, g = vg(p, *xy)
                m = jax.tree.map(lambda mi, gi: beta * mi + (1 - beta) * gi,
                                 m, self.mask(g))
                p = self.store(jax.tree.map(lambda pi, mi: pi - lr * mi, p, m))
                return (p, m), None

            (p, _), _ = jax.lax.scan(body, (p, jax.tree.map(jnp.zeros_like,
                                                            p)), (xs, ys))
            return p

        def server_train(p, xs, ys, lr):
            """The FedDU server's plain SGD steps; the first one's accuracy."""
            def body(p, xy):
                (_, acc), g = vg(p, *xy)
                p = self.store(jax.tree.map(lambda pi, gi: pi - lr * gi, p,
                                            self.mask(g)))
                return p, acc

            p, accs = jax.lax.scan(body, p, (xs, ys))
            return p, accs[0]

        self._local = jax.jit(local_train)
        self._local_all = jax.jit(jax.vmap(local_train,
                                           in_axes=(None, 0, 0, None)))
        self._server = jax.jit(server_train)
        self.evaluate = jax.jit(
            lambda p, x, y: loss_and_acc(p, x, y, self.fmask, precision))

    def init_state(self, params) -> dict:
        p = self.mask(jax.tree.map(lambda a: a.astype(jnp.float32), params))
        return {"params": self.store(p),
                "server_m": jax.tree.map(jnp.zeros_like, p), "round": 0}

    def round(self, state: dict, b: dict) -> dict:
        hp = self.hp
        lr = hp["lr"] * hp["lr_decay"] ** state["round"]
        params = state["params"]
        sizes = np.asarray(b["sizes"], np.float64)
        w = sizes / sizes.sum()
        clients = b["cx"].shape[0]
        nbytes = 4 * sum(a.size for a in jax.tree.leaves(params))
        if clients * nbytes < VMAP_CLIENT_BYTES:
            trained = self._local_all(params, b["cx"], b["cy"], lr)
            w_half = jax.tree.map(
                lambda a: jnp.einsum("c,c...->...", jnp.asarray(w, a.dtype),
                                     a, precision=jax.lax.Precision.HIGHEST),
                trained)
        else:
            w_half = None
            for c in range(clients):
                p = self._local(params, b["cx"][c], b["cy"][c], lr)
                part = jax.tree.map(lambda a, wc=float(w[c]): wc * a, p)
                w_half = part if w_half is None else jax.tree.map(
                    jnp.add, w_half, part)
        w_half = self.store(w_half)
        tau = b["sx"].shape[0]
        p, acc0 = self._server(w_half, b["sx"], b["sy"], lr)
        du = hp["feddu"]
        num = float(b["n0"]) * float(b["d_round"])
        den = num + float(sizes.sum()) * float(b["d_server"]) + du["eps"]
        t_eff = ((1.0 - float(acc0)) * (num / den) * du["C"]
                 * du["decay"] ** state["round"] * tau)
        proposed = self.store(jax.tree.map(
            lambda wh, we: wh - t_eff * lr * (wh - we) / (tau * lr),
            w_half, p))
        bs, eta_s = hp["feddum"]["beta_server"], hp["feddum"]["eta_server"]
        m_s = jax.tree.map(lambda mi, a, b_: bs * mi + (1 - bs) * (a - b_),
                           state["server_m"], params, proposed)
        new = self.store(jax.tree.map(lambda a, mi: a - eta_s * mi, params,
                                      m_s))
        return {"params": self.mask(new), "server_m": self.mask(m_s),
                "round": state["round"] + 1}


# ---------------------------------------------------------------------------
# readings and the numbers compared
# ---------------------------------------------------------------------------

@jax.jit
def leaf_norms(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32)))) for k, v in flat}


@jax.jit
def change_norms(after, before):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before))


def host(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}


def norm_gap(got: dict, want: dict) -> tuple[float, str, list]:
    """Worst leaf's |norm(program) - norm(reference)| over the reference's
    norm of that leaf or of the median leaf, whichever is larger.  Leaves
    the reference leaves at under a thousandth of the median leaf are left
    out (their norms are round-off).  Returns (gap, leaf, leaves left out).
    """
    med = float(np.median(list(want.values())))
    out = [k for k, v in want.items() if v < 1e-3 * med]
    worst, leaf = 0.0, ""
    for k, v in want.items():
        if k in out:
            continue
        gap = abs(got[k] - v) / max(v, med)
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf, out


def compare(got: dict, want: dict) -> dict:
    """The three numbers of a training cell: each step's eval loss, the
    server momentum after the first step (the first gradient as the
    server optimizer holds it) and the parameters' change over the steps.
    """
    loss = max(abs(g - w) / abs(w) for g, w in zip(got["loss"],
                                                   want["loss"]))
    first, first_leaf, first_out = norm_gap(got["first_m"], want["first_m"])
    change, change_leaf, change_out = norm_gap(got["change"], want["change"])
    return {"loss_rel_gap": loss, "first_m_gap": first,
            "change_gap": change,
            "where": {"first_m": first_leaf, "change": change_leaf,
                      "left_out": sorted(set(first_out) | set(change_out))}}


def run_reference(ref: Reference, params_bf16, data_dev: dict, key,
                  hp: dict, steps: int) -> dict:
    """``steps`` window steps (chunks of ``hp["chunk"]`` rounds, then an
    eval) from ``params_bf16`` on the key chain from ``key``: the same
    readings the harness takes from the program."""
    state = ref.init_state(params_bf16)
    start = state["params"]
    kw = dict(clients=hp["clients_per_round"], batch=hp["batch_size"],
              steps=hp["local_steps"], server_batch=hp["server_batch_size"],
              tau=hp["tau"])
    read = {"loss": []}
    for step in range(steps):
        for _ in range(hp["chunk"]):
            key, sub = jax.random.split(key)
            state = ref.round(state, draw_round(sub, data_dev, **kw))
        loss, _ = ref.evaluate(state["params"], data_dev["test_x"],
                               data_dev["test_y"])
        read["loss"].append(float(loss))
        if step == 0:
            read["first_m"] = host(leaf_norms(state["server_m"]))
    read["change"] = host(change_norms(state["params"], start))
    return read
