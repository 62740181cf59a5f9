"""Of the layers a prefill's positions could walk, the share they did:
``chunk_layer_positions`` over ``prefill_positions`` times the model's
depth (``stats()``, as differences across the window; positions of chunk
programs and bucketed prefills alike, padding included).  A chunk's rows
write caches and give no logits, so they stop where the caches stop: in a
model whose later layers keep none (gated memory units, cross attention to
an earlier layer's row) they walk the layers before the last that keeps
one, write that layer's keys and values and leave, 17 of 32 layers at the
published depth.  Every other model reads 100.  None where the program has
no such counter, or the window prefilled nothing.
"""
LAYER = "programs"
SOURCE = "program_counter"
MOVES = "serve_itl_p95_ms"
DEVICE = False   # True: only a chip run can give it


def read(obs):
    from harness import program_spans as ps
    walked = ps.delta(obs, "chunk_layer_positions")
    positions = ps.delta(obs, "prefill_positions")
    if walked is None or positions is None:
        return None
    return ps.ratio(walked, positions * obs["cfg"]["num_hidden_layers"],
                    100.0)
