"""Device time of the key-sharded step's selector per micro-batch of the chunk
program, on the first device: scope `selector`, and with it the operations under
`q.<query>` that carry no inner scope of the engine. On the mesh the compiler's
own expansions of the group-by (`.../q.<query>/shard_map/sort.N`,
`reduce-window.N`, seen in the program compiled for a v5e:2x2) lose the scope
they came from; the step's other stages (`filter`, `keyshard.route`,
`keyshard.exchange`) name theirs, and `route_device_ms` is taken off, since
`program_spans` files that scope's operations under `q.<query>` alone. Device
trace."""

import harness
import program_spans
import readers


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    runs = len(readers.chunk_executions(trace))
    depth = readers.chunk_batches(counters, cell)
    table = ps.device_ms_by_scope(readers.CHUNK_PROGRAM) if ps and runs else None
    if table is None or not depth:
        return None
    step = "q." + cell["config"]["query"]
    ms = sum(v for path, v in table.items()
             if path == step or "selector" in path.split("/"))
    route = harness.load_module(
        harness.reader_file(cell["bench_dir"], "route_device_ms.keys4"))
    return ms / (runs * depth) - (route.read(trace, spans, counters, cell) or 0.0)
