"""What the tests of whole-layer recomputation share: the Pallas calls a
traced program holds, by name; one decoder layer's gradients as TrainStep
differentiates it; what its forward saves for its backward; the
recomputation that keeps nothing but the block's input; and the comparison
of two gradients to one ulp."""
import contextlib
import io

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.jit.functional import FunctionalModule


def pallas_calls(fn, *args) -> list:
    """The names of the Pallas calls in fn's jaxpr at `args`, those inside
    sub-jaxprs (a jitted function's `pjit`, a checkpoint, a scan) included,
    sorted."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                names.append(str(getattr(name, "name", name)))
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(names)


def layer_fn(layer):
    """(f(x, params) -> the layer's output, its parameters' values): the
    layer through FunctionalModule in training mode, its buffers as they
    are."""
    fm = FunctionalModule(layer)
    bvals = fm.buffer_values()

    def f(xv, pvals):
        out, _ = fm.call(pvals, bvals, jax.random.PRNGKey(0), (xv,),
                         training=True)
        return out

    return f, fm.param_values()


def layer_grads(layer, x, seed=1):
    """(the gradient function of sum(layer(x) * cot) by x and the
    parameters, jitted: one trace and one compile, its arguments), cot
    drawn from `seed`."""
    f, pvals = layer_fn(layer)
    out = jax.eval_shape(f, x, pvals)
    cot = jax.random.normal(jax.random.PRNGKey(seed), out.shape, jnp.float32)

    def loss(xv, pv):
        return jnp.sum(f(xv, pv).astype(jnp.float32) * cot)

    return jax.jit(jax.grad(loss, argnums=(0, 1))), (x, pvals)


def made_residuals(layer, x) -> list:
    """print_saved_residuals' lines of what the layer's forward MADE for
    its backward: not its arguments, not the norms' constant vectors."""
    f, pvals = layer_fn(layer)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(f, x, pvals)
    return [line for line in out.getvalue().splitlines()
            if "from the argument" not in line
            and "from a constant" not in line]


def plain_checkpoint(apply_full, policy):
    """`_policy_step` as it was before the kernels' outputs were kept:
    "remat" a bare jax.checkpoint that saves the block's input alone."""
    return jax.checkpoint(apply_full) if policy == "remat" else apply_full


def assert_within_one_ulp(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_max_ulp(np.asarray(g), np.asarray(w),
                                        maxulp=1)
