#!/usr/bin/env python3
"""Is float32 division exact on this device where it has to be?

    python benchmarks/tests/division_probe.py        (on the chip)

The TF-IDF graph's pair-presence reduce hands a term id on as the mean
of a constant, ``(c * w) / w`` with ``c < 4096`` a component of the id
and ``w`` the term's count in the article, and the by-term GroupBy makes
an int of it. Run by hand; prints how many pairs ``(c, w)`` come back
below ``c`` (a truncating cast then files the row under the term
before), and from which ``w`` on. On the CPU backend: none while the
product ``c * w`` is itself exact (under 2**24)."""

import jax
import jax.numpy as jnp
import numpy as np

R, W = 4096, 8192


@jax.jit
def quotient(w):
    c = jnp.arange(R, dtype=jnp.float32)[:, None]
    wf = w.astype(jnp.float32)[None, :]
    return (c * wf) / wf


def main() -> None:
    print(jax.devices())
    q = np.asarray(quotient(jnp.arange(1, W + 1, dtype=jnp.int32)))
    c = np.arange(R, dtype=np.float32)[:, None]
    below, above = q < c, q > c
    ws = np.arange(1, W + 1)
    print(f"pairs {q.size}: below {int(below.sum())}, above "
          f"{int(above.sum())}")
    print("smallest w with a quotient below c:", ws[below.any(axis=0)][:10])
    for lim in (32, 64, 256, 1024, 4096, 8192):
        print(f"w <= {lim}: below {int(below[:, :lim].sum())}")


if __name__ == "__main__":
    main()
