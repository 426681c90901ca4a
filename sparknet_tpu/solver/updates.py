"""Solver update rules with exact Caffe semantics.

Re-derives, as pure pytree transforms, the math of the reference solver
hierarchy (solvers/sgd_solver.cpp and siblings):

  order per step (sgd_solver.cpp ApplyUpdate :102-117):
    rate = lr_policy(iter)
    ClipGradients            on RAW summed grads (:81-99)
    per param: Normalize (grad /= iter_size, :119-140)
               Regularize (grad += decay_mult*wd * {w | sign(w)}, :143-205)
               ComputeUpdateValue (per solver type)
    param -= update

  SGD       h = m*h + lr_local*g;            u = h           (:207+)
  Nesterov  h' = m*h + lr_local*g;           u = (1+m)h' - m*h
  AdaGrad   h += g^2;                        u = lr_local * g/(sqrt(h)+delta)
  RMSProp   h = r*h + (1-r)*g^2;             u = lr_local * g/(sqrt(h)+delta)
  AdaDelta  hg = m*hg + (1-m)g^2
            u  = g * sqrt((hu+delta)/(hg+delta))
            hu = m*hu + (1-m)u^2;            u *= lr_local
  Adam      m1 = b1*m1 + (1-b1)g; m2 = b2*m2 + (1-b2)g^2
            u  = lr_local * sqrt(1-b2^t)/(1-b1^t) * m1/(sqrt(m2)+delta)

All state is a per-param list of history arrays, mirroring the reference's
``history_`` blobs so .solverstate interchange is possible.
"""

import jax
import jax.numpy as jnp

SOLVER_TYPES = ("SGD", "Nesterov", "AdaGrad", "RMSProp", "AdaDelta", "Adam")

# number of history slots per param
N_HISTORY = {"SGD": 1, "Nesterov": 1, "AdaGrad": 1, "RMSProp": 1,
             "AdaDelta": 2, "Adam": 2}


def canonical_type(sp):
    """Resolve the solver type string, honoring the deprecated enum
    (reference solver_factory via SolverParameter.type / solver_type)."""
    t = sp.type
    if sp.has("solver_type") and not sp.has("type"):
        t = SOLVER_TYPES[int(sp.solver_type)]
    for s in SOLVER_TYPES:
        if t.lower() == s.lower():
            return s
    raise ValueError(f"unknown solver type {t!r}")


def init_history(solver_type, params):
    n = N_HISTORY[solver_type]
    return jax.tree_util.tree_map(
        lambda p: [jnp.zeros_like(p) for _ in range(n)], params,
        is_leaf=lambda x: hasattr(x, "shape"))


def apply_clip(grads, clip, sumsq):
    """Scale ``grads`` by clip/norm when the global L2 norm exceeds
    ``clip``. Split out of `clip_gradients` so a sharded caller (FSDP)
    can supply the DISTRIBUTED sumsq — shard leaves psum'd over the mesh
    axis — and still get reference clip semantics on the global norm."""
    norm = jnp.sqrt(sumsq)
    scale = jnp.where(norm > clip, clip / jnp.maximum(norm, 1e-30), 1.0)
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def clip_gradients(grads, clip):
    """Global L2-norm clipping (sgd_solver.cpp:81-99); clip < 0 disables."""
    if clip is None or clip < 0:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    sumsq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves)
    return apply_clip(grads, clip, sumsq)


def accum_init(params):
    """fp32 gradient accumulators for the iter_size micro-batch loop:
    the mixed-precision contract (Micikevicius et al., 2018) sums
    micro-grads in fp32 even when params or compute are bf16/fp16.
    fp32 params already accumulate in fp32, so this is bit-for-bit the
    old zeros_like path there."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(
            p.shape,
            jnp.float32 if jnp.issubdtype(p.dtype, jnp.floating)
            and jnp.finfo(p.dtype).bits < 32 else p.dtype),
        params)


def accum_add(acc, g):
    """acc + g in the accumulator's (>= fp32) dtype."""
    return jax.tree_util.tree_map(
        lambda a, x: a + x.astype(a.dtype), acc, g)


def regularize(grad, param, wd_local, reg_type):
    if wd_local == 0.0:
        return grad
    if reg_type == "L1":
        return grad + wd_local * jnp.sign(param)
    return grad + wd_local * param  # L2


def compute_update(solver_type, grad, history, local_rate, *, momentum,
                   delta, rms_decay, momentum2, t):
    """-> (update, new_history). ``t`` = iter+1 (Adam bias correction)."""
    g = grad
    if solver_type == "SGD":
        h = momentum * history[0] + local_rate * g
        return h, [h]
    if solver_type == "Nesterov":
        h_new = momentum * history[0] + local_rate * g
        u = (1.0 + momentum) * h_new - momentum * history[0]
        return u, [h_new]
    if solver_type == "AdaGrad":
        h = history[0] + g * g
        u = local_rate * g / (jnp.sqrt(h) + delta)
        return u, [h]
    if solver_type == "RMSProp":
        h = rms_decay * history[0] + (1.0 - rms_decay) * g * g
        u = local_rate * g / (jnp.sqrt(h) + delta)
        return u, [h]
    if solver_type == "AdaDelta":
        hg = momentum * history[0] + (1.0 - momentum) * g * g
        u = g * jnp.sqrt((history[1] + delta) / (hg + delta))
        hu = momentum * history[1] + (1.0 - momentum) * u * u
        return local_rate * u, [hg, hu]
    if solver_type == "Adam":
        m1 = momentum * history[0] + (1.0 - momentum) * g
        m2 = momentum2 * history[1] + (1.0 - momentum2) * g * g
        correction = jnp.sqrt(1.0 - momentum2 ** t) / (1.0 - momentum ** t)
        u = local_rate * correction * m1 / (jnp.sqrt(m2) + delta)
        return u, [m1, m2]
    raise ValueError(solver_type)


class Updater:
    """Bound update transform for one SolverParameter + param-multiplier map.

    mults: pytree congruent to params with (lr_mult, decay_mult) leaves.
    """

    def __init__(self, sp, mults):
        self.solver_type = canonical_type(sp)
        self.momentum = float(sp.momentum) if sp.has("momentum") else 0.0
        self.momentum2 = float(sp.momentum2)
        self.delta = float(sp.delta)
        self.rms_decay = float(sp.rms_decay) if sp.has("rms_decay") else 0.99
        self.weight_decay = float(sp.weight_decay) \
            if sp.has("weight_decay") else 0.0
        self.reg_type = sp.regularization_type
        self.clip = float(sp.clip_gradients)
        self.iter_size = int(sp.iter_size)
        self.mults = mults

    def init(self, params):
        return init_history(self.solver_type, params)

    @jax.named_scope("update")      # the optimizer's share of a trace
    def __call__(self, params, grads, history, rate, it, clip_fn=None):
        """One update: returns (new_params, new_history).

        ``rate`` is the policy lr for this iter; ``it`` the iter index
        (both may be traced). ``clip_fn`` replaces the default global
        L2 clip — a sharded solver passes one that computes the norm
        over the whole mesh (see parallel/fsdp.py); None keeps the
        reference `clip_gradients` path bit-for-bit.
        """
        grads = clip_fn(grads) if clip_fn is not None \
            else clip_gradients(grads, self.clip)
        t = it + 1
        new_params, new_history = {}, {}
        for lname, blobs in params.items():
            ups, hs = [], []
            for i, p in enumerate(blobs):
                g = grads[lname][i].astype(p.dtype)
                lr_mult, decay_mult = self.mults[lname][i]
                if self.iter_size > 1:
                    g = g / self.iter_size
                g = regularize(g, p, self.weight_decay * decay_mult,
                               self.reg_type)
                local_rate = rate * lr_mult
                u, h = compute_update(
                    self.solver_type, g, history[lname][i], local_rate,
                    momentum=self.momentum, delta=self.delta,
                    rms_decay=self.rms_decay, momentum2=self.momentum2, t=t)
                ups.append(p - u)
                hs.append(h)
            new_params[lname] = ups
            new_history[lname] = hs
        return new_params, new_history
