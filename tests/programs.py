"""The model's two forwards (and its seeded weights) jitted: one compiled
program a shape and set of static options, shared by every test of a
worker's file that asks the same (docs/testing.md "Conventions").

An EAGER ``llama.forward`` compiles its layer scans anew every call (a
scan's body is a new jaxpr each trace), and through the interpreted
kernels one such compile is 10-20 s of lowering: five decode steps of one
shape were five compiles. A test takes these where it only reads what the
forward gives; a test that patches the model's own functions clears JAX's
caches (``jax.clear_caches()``) around its faulty call, as before, so a
sound program does not answer for the faulty one, and stands at the end of
its file so that the tests before it keep theirs.
"""

import jax

from dynamo_exp_tpu.models import llama

_FORWARD_STATIC = ("attn_pages", "attn_impl", "mesh", "interpret", "count_picks")
_RAGGED_STATIC = (*_FORWARD_STATIC, "q_tile", "packed_from", "project")

# The seeded weights as ONE program a configuration: eager, every array of the
# tree is a draw compiled by itself (8 s for a tiny model; 4 s jitted). The
# values are the eager ones to float32 rounding, not to the bit: a test holds
# them against a reference computed from the SAME tree, never against an
# engine's own (``TPUEngine`` draws eagerly).
init_params = jax.jit(llama.init_params, static_argnums=(1,))
forward = jax.jit(llama.forward, static_argnums=(1,), static_argnames=_FORWARD_STATIC)
forward_ragged = jax.jit(
    llama.forward_ragged, static_argnums=(1,), static_argnames=_RAGGED_STATIC
)
