"""Bad fixture: jit-purity — host side effects frozen into traces."""
import random
import time

import jax
import numpy as np

COUNTER = 0


@jax.jit
def stamped(x):
    return x * time.time()  # clock read at trace time only


def noisy(x):
    print("tracing", x)  # prints once, at trace time
    return x + np.random.rand()  # unseeded global draw


def run(xs):
    return jax.vmap(noisy)(xs)


def helper(x):
    return x * random.random()  # unseeded draw, one call level deep


@jax.jit
def indirect(x):
    return helper(x)


@jax.jit
def mutator(x):
    global COUNTER
    COUNTER += 1  # mutation runs at trace time only
    return x


def scanned(xs):
    def body(carry, x):
        rng = np.random.default_rng()  # constructed without a seed
        return carry + rng.standard_normal(), carry

    return jax.lax.scan(body, 0.0, xs)


@jax.jit
def annotated(x):
    with jax.profiler.TraceAnnotation("span"):  # opened at trace time only
        return x + 1
