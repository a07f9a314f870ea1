"""Device time of the key-sharded step's merge (scope `keyshard.exchange`: the
`psum`s that fold the owners' output rows and flags over the keys mesh) per
micro-batch of the chunk program, on the first device. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, "keyshard.exchange")
