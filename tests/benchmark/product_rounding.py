"""A function evaluated the way a TPU's default matmul precision evaluates
it, on the CPU: the operands of every f32 matrix product rounded to
bfloat16, the sums in f32. The check's rule (c) allows the program four
times the MEAN distance between the reference's two precisions and reads
the WORST element, which holds only while the program's products round the
values the plain reference's do (PERF.md section 7): this is how a test
sees, without a chip, where they do not.

`with_rounded_products(f)` traces `f` to a jaxpr and evaluates it equation
by equation, rounding what a product reads; calls, checkpoints, branches
and scans are walked into. It changes nothing outside the call."""

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core as jax_core

PRODUCTS = ("dot_general", "ragged_dot", "ragged_dot_general")
CALLS = {"jit": "jaxpr", "closed_call": "call_jaxpr", "remat2": "jaxpr",
         "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr"}


def _rounded(x):
    if x.dtype != jnp.float32:
        return x
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _closed(jaxpr):
    """(jaxpr, consts) of a closed or an open jaxpr."""
    if hasattr(jaxpr, "consts"):
        return jaxpr.jaxpr, jaxpr.consts
    return jaxpr, ()


def _has_product(value):
    """Whether a parameter of an equation is a program with a product."""
    if isinstance(value, (list, tuple)):
        return any(_has_product(v) for v in value)
    jaxpr = getattr(value, "jaxpr", value)
    return any(eqn.primitive.name in PRODUCTS
               or any(_has_product(v) for v in eqn.params.values())
               for eqn in getattr(jaxpr, "eqns", ()))


def _evaluate(jaxpr, consts, *args):
    env = dict(zip(jaxpr.constvars, consts)) | dict(zip(jaxpr.invars, args))

    def read(var):
        return var.val if isinstance(var, jax_core.Literal) else env[var]

    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        name, params = eqn.primitive.name, eqn.params
        if name in PRODUCTS:
            outs = eqn.primitive.bind(_rounded(ins[0]), _rounded(ins[1]),
                                      *ins[2:], **params)
        elif name in CALLS:
            outs = _evaluate(*_closed(params[CALLS[name]]), *ins)
        elif name == "cond":
            outs = lax.switch(ins[0], [
                (lambda *a, b=b: _evaluate(*_closed(b), *a))
                for b in params["branches"]], *ins[1:])
        elif name == "scan":
            n_consts, n_carry = params["num_consts"], params["num_carry"]
            body, fixed = _closed(params["jaxpr"]), ins[:n_consts]

            def step(carry, xs, body=body, fixed=fixed, n_carry=n_carry):
                out = _evaluate(*body, *fixed, *carry, *xs)
                return tuple(out[:n_carry]), tuple(out[n_carry:])

            carry, ys = lax.scan(
                step, tuple(ins[n_consts:n_consts + n_carry]),
                tuple(ins[n_consts + n_carry:]), length=params["length"],
                reverse=params["reverse"])
            outs = [*carry, *ys]
        else:
            # an equation that carries a program this walk does not know
            # would keep its products' operands whole, and say nothing
            if any(_has_product(v) for v in params.values()):
                raise NotImplementedError(
                    f"{name} carries a program with a product: teach "
                    "product_rounding to walk into it")
            outs = eqn.primitive.bind(*ins, **params)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def with_rounded_products(f):
    """`f(*arrays and pytrees)` with every product's operands rounded."""
    def rounded(*args):
        closed, shape = jax.make_jaxpr(f, return_shape=True)(*args)
        out = _evaluate(closed.jaxpr, closed.consts, *jax.tree.leaves(args))
        return jax.tree.unflatten(jax.tree.structure(shape), out)
    return rounded
