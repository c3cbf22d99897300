"""Pure-jnp oracle for the silumul kernel: the XLA runner of the plan's
``silumul`` op, which reads the exp2 table with an XLA gather."""

from repro.models import layers as L


def silumul_ref(g_q, u_q, *, scales):
    return L.silumul_int(g_q, u_q, L.silumul_params(*scales))
