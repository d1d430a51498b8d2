"""Plain float32 training of the dense GQA decoder that ``dense.py``
writes (Qwen3: RMSNorm, QK-norm, RoPE, SwiGLU, tied head): the loss, its
gradient and AdamW, in ``jax.numpy`` at the highest matmul precision,
independent of the program.

The weights are ``dense.py``'s, drawn from the seed with the program's
keys; the norm weights (before attention and the MLP, the QK-norms and
the final norm) start at one, as the program's do, and are trained with
the rest.  The forward reads the weights as stored (bf16); AdamW updates
the float32 master, after which the stored weights are rounded to bf16
again, as a bf16 model with an f32 master is.

It runs in blocks, so that one pipeline stage of Qwen3-4B (1.3B
parameters: 20.8 GB of master, moments and gradient) fits a four-chip
host: layer by layer, each layer's input stashed and its gradient taken
by ``jax.vjp`` of that layer alone; attention in blocks of queries, each
against the keys up to its own end; the loss in blocks of positions
(whole float32 logits at 16 x 4096 tokens would be 40 GB).  The rows of
the batch are spread over the devices JAX sees (as many as divide
them); master, moments and gradients are sharded over them.

``precision`` names the arithmetic, forward and backward, as in
``ssm.py``: ``"f32"`` is the reference; ``"bf16"`` rounds every tensor
the program holds in bf16 (the residual stream, norm and projection
outputs, q/k/v and the attention output, the SwiGLU product, logits,
matmul and attention operands) to bf16, a witness of sound bf16
arithmetic computed independently of the program; ``"fp8"`` is the
control, one step below the configuration's bf16: matmul and attention
operands in float8_e4m3 with a per-tensor scale, the rest in bf16.
``rows`` limits the batch to its first rows (half the batch left out).
"""
from __future__ import annotations

import functools
import sys
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import dense
from bench.reference.ssm import act, matmul, q, rms

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512      # queries per attention block
T_BLOCK = 512      # positions per block of the loss
STACK = "['stacks'][0]"


def init_layer(c: Dict, key, i):
    """Layer ``i`` as the program's stacked block holds it."""
    w = dense.layer_weights(c, key, i)
    d, dh = c["hidden_size"], c["head_dim"]
    return {"ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"], "q_norm": jnp.ones((dh,), jnp.float32),
                     "k_norm": jnp.ones((dh,), jnp.float32)},
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]}}


def attention(qh, kh, vh, precision: str):
    """Causal GQA over q (R,T,H,dh), k/v (R,T,KV,dh) -> (R,T,H*dh), one
    block of queries at a time against the keys up to its end."""
    R, T, H, dh = qh.shape
    KV = kh.shape[2]
    G = H // KV

    def block(start, qb, kb, vb):
        n, e = qb.shape[1], kb.shape[1]
        qg = q(qb, precision).reshape(R, n, KV, G, dh)
        s = jnp.einsum("rskgd,rtkd->rkgst", qg, q(kb, precision),
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(dh))
        causal = (start + jnp.arange(n))[:, None] >= jnp.arange(e)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("rkgst,rtkd->rskgd", q(pr, precision),
                       q(vb, precision), precision=HIGHEST)
        return act(o.reshape(R, n, H * dh), precision)

    bq = min(Q_BLOCK, T)
    return jnp.concatenate(
        [jax.checkpoint(functools.partial(block, s))(
            qh[:, s:s + bq], kh[:, :s + bq], vh[:, :s + bq])
         for s in range(0, T, bq)], axis=1)


def layer(w, x, c: Dict, precision: str):
    """One decoder layer over the rows x (R, T, d)."""
    R, T, _ = x.shape
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta, pos = c["rms_norm_eps"], c["rope_theta"], jnp.arange(T)
    a, m = w["attn"], w["mlp"]
    h = act(rms(x, w["ln1"], eps), precision)
    qh = act(rms(matmul(h, a["wq"], precision).reshape(R, T, H, dh),
                 a["q_norm"], eps), precision)
    kh = act(rms(matmul(h, a["wk"], precision).reshape(R, T, KV, dh),
                 a["k_norm"], eps), precision)
    vh = matmul(h, a["wv"], precision).reshape(R, T, KV, dh)
    qh = act(dense.rope(qh, pos, theta), precision)
    kh = act(dense.rope(kh, pos, theta), precision)
    o = attention(qh, kh, vh, precision)
    x = act(x + matmul(o, a["wo"], precision), precision)
    h = act(rms(x, w["ln2"], eps), precision)
    ff = act(act(jax.nn.silu(matmul(h, m["w_gate"], precision)), precision)
             * matmul(h, m["w_up"], precision), precision)
    return act(x + matmul(ff, m["w_down"], precision), precision)


def nll_sum(x, final_norm, emb, labels, c: Dict, precision: str):
    """Sum over the rows' positions of the tied head's cross entropy,
    one block of positions at a time (its logits made again on the way
    back)."""
    h = act(rms(x, final_norm, c["rms_norm_eps"]), precision)

    @jax.checkpoint
    def block(total, hl):
        hb, lb = hl
        logits = act(jnp.einsum("rtd,vd->rtv", q(hb, precision),
                                q(emb, precision), precision=HIGHEST),
                     precision)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold), None

    R, T, d = h.shape
    bt = min(T_BLOCK, T)
    blocks = (jnp.moveaxis(h.reshape(R, T // bt, bt, d), 1, 0),
              jnp.moveaxis(labels.reshape(R, T // bt, bt), 1, 0))
    return jax.lax.scan(block, jnp.float32(0.0), blocks)[0]


class _Stage:
    """The jitted pieces of one precision over one row count: rows
    sharded over the devices, weights replicated where they are used
    and sharded where they are kept."""

    def __init__(self, c: Dict, precision: str, n_rows: int):
        devs = jax.devices()
        n = max(k for k in range(1, len(devs) + 1) if n_rows % k == 0)
        self.mesh = Mesh(np.array(devs[:n]), ("rows",))
        self.rows = NamedSharding(self.mesh, P("rows"))
        rep = NamedSharding(self.mesh, P())

        def stored(tree):
            """The weights as the forward reads them: bf16, on every
            device."""
            return jax.tree.map(lambda a: jax.lax.with_sharding_constraint(
                dense._bf16(a), rep), tree)

        def by_row(x):
            """(R, ...) rows -> (R/n, n, ...): one row of each device at
            a time, so that a layer's activations are one row's."""
            return jnp.swapaxes(x.reshape(n, -1, *x.shape[1:]), 0, 1)

        def rows_of(xs):
            return jnp.swapaxes(xs, 0, 1).reshape(-1, *xs.shape[2:])

        def fwd(w, x):
            ws = stored(w)
            return rows_of(jax.lax.map(lambda r: layer(ws, r, c, precision),
                                       by_row(x)))

        def bwd(w, x, g):
            ws = stored(w)

            def one(gw, xg):
                _, vjp = jax.vjp(lambda w, x: layer(w, x, c, precision),
                                 ws, xg[0])
                gw_r, gx = vjp(xg[1])
                return jax.tree.map(jnp.add, gw, gw_r), gx

            gw, gx = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, ws),
                                  (by_row(x), by_row(g)))
            return gw, rows_of(gx)

        def embed(emb, toks):
            return act(stored(emb)[toks], precision)

        def head(x, fn, emb, labels, scale):
            total, vjp = jax.vjp(
                lambda x, fn, emb: nll_sum(x, fn, emb, labels, c, precision),
                x, stored(fn), stored(emb))
            return (total,) + vjp(scale)

        def head_loss(x, fn, emb, labels):
            return nll_sum(x, stored(fn), stored(emb), labels, c, precision)

        def embed_grad(g_head, toks, g):
            return g_head.at[toks].add(g)

        key_shards = self.sharding
        lyr = jax.eval_shape(lambda k: init_layer(c, k, 0),
                             jax.random.PRNGKey(0))
        emb = jax.eval_shape(lambda k: dense.embedding(c, k),
                             jax.random.PRNGKey(0))
        self.fwd = jax.jit(fwd, out_shardings=self.rows)
        self.bwd = jax.jit(bwd, out_shardings=(key_shards(lyr), self.rows))
        self.embed = jax.jit(embed, out_shardings=self.rows)
        self.norm = jax.ShapeDtypeStruct((c["hidden_size"],), jnp.float32)
        self.head = jax.jit(head, out_shardings=(
            rep, self.rows, key_shards(self.norm), key_shards(emb)))
        self.head_loss = jax.jit(head_loss)
        self.embed_grad = jax.jit(embed_grad, out_shardings=key_shards(emb),
                                  donate_argnums=0)
        self.init_layer = jax.jit(lambda k, i: init_layer(c, k, i),
                                  out_shardings=key_shards(lyr))
        self.init_embed = jax.jit(lambda k: dense.embedding(c, k),
                                  out_shardings=key_shards(emb))

    def sharding(self, tree):
        """Each leaf sharded on its first dimension where the devices
        divide it, else replicated."""
        n = self.mesh.size
        return jax.tree.map(lambda a: NamedSharding(
            self.mesh, P("rows") if a.shape and a.shape[0] % n == 0
            else P()), tree)

    def put_rows(self, a):
        return jax.device_put(jnp.asarray(a), self.rows)

    def losses_and_grads(self, w, toks, labs):
        """The mean loss of the rows and its gradient: (loss, {"embed",
        "final_norm", "layers": [per layer]})."""
        toks, labs = self.put_rows(toks), self.put_rows(labs)
        x = self.embed(w["embed"], toks)
        stash = []
        for lw in w["layers"]:
            stash.append(x)
            x = self.fwd(lw, x)
        total, g, g_fn, g_emb = self.head(
            x, w["final_norm"], w["embed"], labs,
            jnp.float32(1.0 / labs.size))
        grads: List = [None] * len(stash)
        for i in reversed(range(len(stash))):
            grads[i], g = self.bwd(w["layers"][i], stash.pop(), g)
        return (float(total) / labs.size,
                {"embed": self.embed_grad(g_emb, toks, g),
                 "final_norm": g_fn, "layers": grads})

    def loss(self, w, toks, labs):
        toks, labs = self.put_rows(toks), self.put_rows(labs)
        x = self.embed(w["embed"], toks)
        for lw in w["layers"]:
            x = self.fwd(lw, x)
        return float(self.head_loss(x, w["final_norm"], w["embed"],
                                    labs)) / labs.size

    def init(self, c: Dict, seed: int):
        """The seed's initial weights, as kept: sharded."""
        key = jax.random.PRNGKey(seed)
        return {"embed": self.init_embed(key),
                "final_norm": jax.device_put(
                    jnp.ones(self.norm.shape, jnp.float32),
                    self.sharding(self.norm)),
                "layers": [self.init_layer(key, i)
                           for i in range(c["num_hidden_layers"])]}


def _program_paths(tree) -> Dict[str, object]:
    """``{"embed", "final_norm", "layers": [...]}`` as the program's
    parameter paths (``jax.tree_util.keystr``), per-layer leaves stacked
    along a leading axis: the layers' list of arrays for each."""
    out = {"['embed']": tree["embed"], "['final_norm']": tree["final_norm"]}
    for path, _ in jax.tree_util.tree_flatten_with_path(tree["layers"][0])[0]:
        key = STACK + jax.tree_util.keystr(path)
        out[key] = [functools.reduce(lambda t, k: t[k.key], path, lw)
                    for lw in tree["layers"]]
    return out


@jax.jit
def _sq(a):
    return jnp.sum(jnp.square(a))


def initial_losses(c: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]]) -> List[float]:
    """Each batch's loss at the seed's initial weights (the losses of a
    step that leaves its state unchanged)."""
    with jax.default_matmul_precision("highest"):
        stage = _Stage(c, "f32", len(batches[0]["tokens"]))
        w = stage.init(c, seed)
        return [stage.loss(w, b["tokens"], b["labels"]) for b in batches]


def train_readings(c: Dict, traffic: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]],
                   precision: str = "f32", rows: int = 0) -> Dict:
    """Per-step losses, the first gradient per leaf and each leaf's change
    (norm) after ``len(batches)`` AdamW steps.  The forward reads the
    stored weights; AdamW updates the float32 master."""
    o = traffic["optimizer"]
    n = rows or len(batches[0]["tokens"])
    t0 = time.perf_counter()

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adamw(p, g, m, v, clip, step):
        b1c = 1.0 - o["beta1"] ** step
        b2c = 1.0 - o["beta2"] ** step
        g = g * clip
        m = o["beta1"] * m + (1.0 - o["beta1"]) * g
        v = o["beta2"] * v + (1.0 - o["beta2"]) * g * g
        p = p - o["lr"] * ((m / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
                           + o["weight_decay"] * p)
        return p, m, v

    with jax.default_matmul_precision("highest"):
        stage = _Stage(c, precision, n)
        master = stage.init(c, seed)
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)
        losses: List[float] = []
        first = None
        for step, b in enumerate(batches, 1):
            loss, grads = stage.losses_and_grads(
                master, b["tokens"][:n], b["labels"][:n])
            losses.append(loss)
            if first is None:                   # kept on the host
                first = {k: np.stack([np.asarray(a) for a in x])
                         if isinstance(x, list) else np.asarray(x)
                         for k, x in _program_paths(grads).items()}
            gn = float(np.sqrt(sum(float(_sq(a)) for a in
                                   jax.tree_util.tree_leaves(grads))))
            clip = jnp.float32(min(1.0, o["grad_clip"] / (gn + 1e-9)))
            out = jax.tree.map(
                lambda p, g, m_, v_: adamw(p, g, m_, v_, clip,
                                           jnp.float32(step)),
                master, grads, m, v)
            del grads
            master, m, v = (jax.tree.map(lambda _, t: t[i], master, out)
                            for i in range(3))
        del m, v
        p0 = stage.init(c, seed)
        diff = jax.tree.map(lambda a, b: float(_sq(a - b)), master, p0)
    change = {k: float(np.sqrt(sum(x) if isinstance(x, list) else x))
              for k, x in _program_paths(diff).items()}
    print(f"reference ({precision}, {n} rows, {len(batches)} steps): "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    return {"losses": losses, "grads": first, "change_norms": change}
