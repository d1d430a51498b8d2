"""Plain float32 Mamba2 language model (SSD, arXiv:2405.21060), written
from the paper and independent of the program: initialisation from the
seed, the loss, its gradient and AdamW, in ``jax.numpy`` at the highest
matmul precision.

Block: x + mixer(rmsnorm(x)).  Mixer: in_proj -> (z, xBC, dt); depthwise
causal conv + SiLU on xBC; x, B, C split (one group); dt = softplus(dt +
dt_bias); the SSD recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,
y_t = C_t h_t + D x_t, computed chunk by chunk as in the paper's minimal
SSD listing; y * SiLU(z), rmsnorm, out_proj.  Final rmsnorm and a tied
head.  The seed's weights are drawn as the published recipe of the
configuration states them (normal, 1/sqrt(fan-in), bf16 storage) with
the same keys, so that the reference starts from the weights the
program serves; it reads none of them from the program.

The weights the forward reads are those the configuration stores:
bf16, except ``A_log``, ``D`` and ``dt_bias`` (float32, as the released
Mamba2 keeps them); after each AdamW step on the float32 master copy
they are rounded to that storage again, as a bf16 model with an f32
master is.  ``dt_bias`` starts as Mamba2 initialises it where the
configuration gives the dt range (``time_step_min``/``max``).

``precision`` names the arithmetic, forward and backward: ``"f32"``
is the reference; ``"bf16"`` rounds every tensor the program holds in
bf16 (the residual stream, norm, projection, conv and SSD outputs,
matmul and SSD operands) to bf16, a second witness for the program
computed independently of it; ``"fp8"`` is the control, one step below
the configuration's bf16: matmul and SSD operands in float8_e4m3 with a
per-tensor scale, the rest in bf16, as fp8 training holds them.
``rows`` limits the batch to its first rows (half the batch left out).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SSD_CHUNK = 128


def _bf16(x):
    """Round to bf16, kept in float32 (an explicit op: XLA may drop a
    convert to bf16 and back as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dense(key, d_in: int, d_out: int):
    s = 1.0 / jnp.sqrt(d_in)
    return _bf16(jax.random.normal(key, (d_in, d_out), jnp.float32) * s)


def dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    di = c["expand"] * d
    return dict(d=d, di=di, N=c["state_size"], H=c["n_heads"],
                P=c["head_dim"], K=c["conv_kernel"], V=c["vocab_size"],
                L=c["num_hidden_layers"])


def init(c: Dict, key):
    g = dims(c)
    d, di, N, H, K = g["d"], g["di"], g["N"], g["H"], g["K"]
    ks = jax.random.split(key, 8)
    conv_dim = di + 2 * N
    dt_bias = jnp.zeros((H,), jnp.float32)
    if "time_step_min" in c:       # dt log-uniform, at midpoint quantiles
        lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
        dt = jnp.exp(lo + (hi - lo) * (jnp.arange(H) + 0.5) / H)
        dt_bias = dt + jnp.log(-jnp.expm1(-dt))

    def layer(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": jnp.ones((d,), jnp.float32),
            "ssm": {
                "in_proj": _dense(k1, d, 2 * di + 2 * N + H),
                "conv_w": _bf16(jax.random.normal(k2, (K, conv_dim),
                                                  jnp.float32)
                                / math.sqrt(K)),
                "conv_b": jnp.zeros((conv_dim,), jnp.float32),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, H,
                                              dtype=jnp.float32)),
                "D": jnp.ones((H,), jnp.float32),
                "dt_bias": dt_bias,
                "norm_w": jnp.ones((di,), jnp.float32),
                "out_proj": _dense(k3, di, d),
            },
        }

    return {
        "embed": _bf16(jax.random.normal(ks[0], (g["V"], d), jnp.float32)
                       * 0.02),
        "final_norm": jnp.ones((d,), jnp.float32),
        "stacks": [jax.vmap(layer)(jax.random.split(ks[1], g["L"]))],
    }


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_ROUND = {"bf16": _bf16, "fp8": _fp8}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake(x, fmt: str):
    """Round to ``fmt``, and the cotangent that flows back through it."""
    return _ROUND[fmt](x)


_fake.defvjp(lambda x, fmt: (_ROUND[fmt](x), None),
             lambda fmt, _, g: (_ROUND[fmt](g),))


def q(x, precision: str):
    """A matmul or SSD operand, in ``precision``."""
    return x if precision == "f32" else _fake(x, precision)


def act(x, precision: str):
    """Any other tensor the program holds in bf16: bf16 but in float32."""
    return x if precision == "f32" else _fake(x, "bf16")


F32_LEAVES = ("A_log", "D", "dt_bias")


def stored(params):
    """The weights as stored: bf16 but for ``F32_LEAVES``."""
    def one(path, x):
        return x if path[-1].key in F32_LEAVES else _bf16(x)

    return jax.tree_util.tree_map_with_path(one, params)


def matmul(a, w, precision: str):
    return act(jnp.matmul(q(a, precision), q(w, precision),
                          precision=HIGHEST), precision)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def segsum(a):
    """out[..., i, j] = a[j+1] + ... + a[i] for j <= i, else -inf."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (T,))      # x[..., i, j]=a[i]
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, precision: str = "f32"):
    """x (b,S,H,P), dt (b,S,H), A (H,), Bm/Cm (b,S,N) -> y (b,S,H,P)."""
    b, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(SSD_CHUNK, S)
    c = S // Q
    Bm, Cm = q(Bm, precision), q(Cm, precision)
    X = q(x * dt[..., None], precision).reshape(b, c, Q, H, P)
    a = (dt * A).reshape(b, c, Q, H).transpose(0, 3, 1, 2)    # (b,H,c,Q)
    Bc, Cc = Bm.reshape(b, c, Q, N), Cm.reshape(b, c, Q, N)
    acs = jnp.cumsum(a, -1)
    Lm = jnp.exp(segsum(a))                                    # (b,H,c,Q,Q)
    CB = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, precision=HIGHEST)
    y_diag = jnp.einsum("bcls,bhcls,bcshp->bclhp", CB, Lm, X,
                        precision=HIGHEST)
    decay = jnp.exp(acs[..., -1:] - acs)                       # (b,H,c,Q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X,
                        precision=HIGHEST)
    states = jnp.concatenate(
        [jnp.zeros((b, 1, H, P, N), jnp.float32), states], axis=1)
    chunk_decay = jnp.exp(segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                        precision=HIGHEST)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(acs),
                       precision=HIGHEST)
    return act((y_diag + y_off).reshape(b, S, H, P), precision)


def mixer(p, h, g, eps, precision):
    b, S, _ = h.shape
    di, N, H, P, K = g["di"], g["N"], g["H"], g["P"], g["K"]
    zxbcdt = matmul(h, p["in_proj"], precision)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = act(jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i]
                              for i in range(K)) + p["conv_b"]), precision)
    xs = xbc[..., :di].reshape(b, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = act(ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, precision)
            + p["D"][:, None] * xs, precision)
    y = act(y.reshape(b, S, di) * act(jax.nn.silu(z), precision), precision)
    return matmul(act(rms(y, p["norm_w"], eps), precision), p["out_proj"],
                  precision)


def loss(params, tokens, labels, c: Dict, precision: str = "f32"):
    g, eps = dims(c), c["layer_norm_epsilon"]
    x = act(params["embed"][tokens], precision)

    @jax.checkpoint
    def block(x, lp):
        h = act(rms(x, lp["ln1"], eps), precision)
        return act(x + mixer(lp["ssm"], h, g, eps, precision),
                   precision), None

    x, _ = jax.lax.scan(block, x, params["stacks"][0])
    logits = matmul(act(rms(x, params["final_norm"], eps), precision),
                    params["embed"].T, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def initial_losses(c: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]]) -> List[float]:
    """Each batch's loss at the seed's initial weights (the losses of a
    step that leaves its state unchanged)."""
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init(c, k))(jax.random.PRNGKey(seed))
        row = jax.jit(lambda p, t, l: loss(p, t[None], l[None], c))
        return [float(np.mean([float(row(params, jnp.asarray(t),
                                         jnp.asarray(l)))
                               for t, l in zip(b["tokens"], b["labels"])]))
                for b in batches]


def _norms(tree) -> Dict[str, float]:
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(x * x)))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_readings(c: Dict, traffic: Dict, seed: int,
                   batches: Sequence[Dict[str, np.ndarray]],
                   precision: str = "f32", rows: int = 0) -> Dict:
    """Per-step losses, the first gradient per leaf and each leaf's change
    (norm) after ``len(batches)`` AdamW steps, the batch taken one row at
    a time.  The forward reads the stored weights; AdamW updates the
    float32 master."""
    o = traffic["optimizer"]
    grad_row = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, t[None], l[None], c, precision)))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adamw(p, g, m, v, step):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        clip = jnp.minimum(1.0, o["grad_clip"] / (gn + 1e-9))
        b1c = 1.0 - o["beta1"] ** step
        b2c = 1.0 - o["beta2"] ** step

        def upd(p, g, m, v):
            g = g * clip
            m = o["beta1"] * m + (1.0 - o["beta1"]) * g
            v = o["beta2"] * v + (1.0 - o["beta2"]) * g * g
            p = p - o["lr"] * ((m / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
                               + o["weight_decay"] * p)
            return p, m, v

        out = jax.tree.map(upd, p, g, m, v)
        pick = lambda i: jax.tree.map(lambda _, t: t[i], p, out)  # noqa: E731
        return pick(0), pick(1), pick(2)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                    donate_argnums=0)
    store = jax.jit(stored)
    with jax.default_matmul_precision("highest"):
        master = jax.jit(lambda k: init(c, k))(jax.random.PRNGKey(seed))
        p0 = jax.tree.map(jnp.copy, master)
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)
        losses: List[float] = []
        first = None
        for step, b in enumerate(batches, 1):
            toks, labs = b["tokens"], b["labels"]
            n = rows or len(toks)
            params = store(master)
            total, gsum = 0.0, None
            for r in range(n):
                lval, g = grad_row(params, jnp.asarray(toks[r]),
                                   jnp.asarray(labs[r]))
                total += float(lval)
                gsum = g if gsum is None else add(gsum, g)
            del params
            grads = scale(gsum, 1.0 / n)
            losses.append(total / n)
            if first is None:                   # kept on the host
                first = {jax.tree_util.keystr(p): np.asarray(g) for p, g in
                         jax.tree_util.tree_flatten_with_path(grads)[0]}
            master, m, v = adamw(master, grads, m, v, jnp.float32(step))
        change = _norms(jax.tree.map(jnp.subtract, master, p0))
    return {"losses": losses, "grads": first, "change_norms": change}
