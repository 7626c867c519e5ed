"""What a model gives the serving engine: the per-layer protocol.

`ServingEngine` knows no architecture. A model that can be served
returns, from `model.served()`, an object with

    max_seq_len, dtype      the longest sequence and the parameters' dtype
    embed(ids, positions)   ids/positions int32 [b, s] -> hidden state h
    layers                  one `ServedLayer` a block, in order
    head(h, at=None)        final norm and vocabulary projection ->
                            logits array [b, s, V]; `at` (traced int32)
                            keeps sequence position `at` alone before
                            the projection -> [b, 1, V]

and every layer has

    cache_kind              a `kv_cache.CacheKind`: the arenas it keeps
    decode(h, pages, view)  one token a slot   -> (h, pages, stats)
    prefill(h, pages, view) one chunk of one request -> (h, pages, stats)

`h` is the model's own (a Tensor, an array): the engine only hands it
from one call to the next. `pages` is the layer's pair of arenas
`(k, v)`, `v` None where the kind has one. There are two families of
cache kind (`kv_cache.CacheKind`): paged by TOKEN, where a layer writes
the step's rows into the pages the view names (`blk`, `off`) and attends
over them through the block table; and rows by REQUEST, where a layer
reads what its request kept at `view.rows` / `view.row` of
`[rows + 1, ...]` arenas and writes it back there (a recurrent state: a
chunk that starts at position 0 starts from zeros, whatever the row
held; padding positions leave it alone. A window layer's ring,
`kv_cache.window_kind`: position p lives in ring row p % window, a step
sees the rows whose position, worked out from the step's own, is >= 0
and inside the window, so a chunk at position 0 starts from an empty
ring whatever the row held and nothing is ever cleared; a chunk reads
the ring before it writes its last real rows into it). `stats` is None
or a dict of float32 scalars the engine sums over the layers and
fetches with the step's tokens (`serving.<name>` counters).

The implementers are `models.gpt.GPTForPretraining` (full K/V),
`models.deepseek_v2.DeepseekV2ForCausalLM` (latent),
`models.granite_hybrid.GraniteHybridForCausalLM` (grouped-query K/V in
its attention layers, request rows in its Mamba-2 layers) and
`models.exaone_moe.ExaoneMoeForCausalLM` (grouped-query K/V paged by
token in its full layers, rings by request in its window layers).
"""
import collections

import jax.numpy as jnp
import numpy as np

# A decode step over S slots. blk/off [S]: the block and the row in it
# that position ctx[s] of slot s is written to; tables [S, max_blocks];
# ctx [S] the position of the step's token (keys 0..ctx are attended);
# live [S] bool: slots that hold a request; use_kernel rides to the
# attention kernels (None: their platform gate); rows [S]: each slot's
# request row (the null row 0 where the slot holds no request, and for
# a model that keeps no rows by request).
DecodeView = collections.namedtuple(
    "DecodeView", "blk off tables ctx live use_kernel rows")

# One chunk of C positions p0..p0+C-1 of one request, the first n_real
# of them real. blk/off [C] (padding rows go to the null block);
# table_row [max_blocks]; positions [C]; live [C] bool; row: the
# request's row.
ChunkView = collections.namedtuple(
    "ChunkView",
    "blk off table_row p0 n_real positions live use_kernel row")


def sum_stats(per_layer):
    """The layers' stats dicts summed key by key and packed into ONE
    float32 array under the names joined by commas, so that the host
    fetches a step's counts in one transfer (`read_stats`); None where
    no layer keeps any."""
    out = {}
    for st in per_layer:
        for name, value in (st or {}).items():
            out[name] = out[name] + value if name in out else value
    if not out:
        return None
    names = sorted(out)
    return {",".join(names): jnp.stack(
        [jnp.asarray(out[n], jnp.float32) for n in names])}


def read_stats(packed):
    """{name: float} of what `sum_stats` packed (fetches the array)."""
    return {name: float(value) for names, values in packed.items()
            for name, value in zip(names.split(","), np.asarray(values))}
