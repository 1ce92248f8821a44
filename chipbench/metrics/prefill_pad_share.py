"""Per-layer metric ``prefill_pad_share`` (engine): share of the prefill
tokens the admission programs computed in the window that no request
needed, in %: duplicate-pad rows of a partial batch bucket and left
padding, from the batcher's counters ``prefill_tokens_computed`` and
``prefill_tokens_needed`` (cached tokens are in neither).  Nothing to read
on a program without them."""

from readers import share


def read(m):
    c = m.counters
    done, need = c.get("prefill_tokens_computed"), \
        c.get("prefill_tokens_needed")
    return None if not done or need is None else share(done - need, done)
