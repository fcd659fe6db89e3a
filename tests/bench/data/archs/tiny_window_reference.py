"""The plain forward of ``tiny_window.py``'s decoder: ``bench/reference.py``'s
dense layer, with a sliding-window layer's query at position p reading
the keys at p - window + 1 .. p only. It imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp

from bench import reference as ref


@functools.partial(jax.jit, static_argnames=("arch", "n_out", "precision"))
def tail_logits(w, tokens, start, *, arch, n_out, precision="fp32"):
    act, quant, prec = ref.precision_of(precision)
    pos = jnp.arange(tokens.shape[0])
    stacks = [w["layers"][f"p{j}"] for j in range(len(arch.pattern))]

    def period(x, lws):
        for kind, lw in zip(arch.pattern, lws):
            x = ref.decoder_layer(
                x, lw, pos, arch=arch, act=act, quant=quant, prec=prec,
                window=arch.window if kind == "local" else None)
        return x, None

    x, _ = jax.lax.scan(period, ref.embed(w, tokens, act, quant), stacks)
    return ref.head_logits(w, x, start, arch=arch, n_out=n_out, act=act,
                           quant=quant, prec=prec)
