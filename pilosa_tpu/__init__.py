"""pilosa_tpu — a TPU-native distributed bitmap index.

A ground-up re-design of Pilosa (reference: /root/reference, Go) for TPU
hardware: host-side storage keeps the reference's roaring snapshot+op-log file
format, while the compute hot path (container intersect/union/andnot/popcount,
TopN) runs as XLA programs over dense packed words held in HBM, and the
per-slice map-reduce is one jitted program over arrays sharded on a
`jax.sharding.Mesh`, with ICI collectives for the reductions.

Layer map (mirrors SURVEY.md §1):
    cli/        command-line verbs (server, import, export, backup, ...)
    server/     HTTP API + server runtime
    pql/        query language lexer/parser/AST
    executor    per-call dispatch + cluster map-reduce
    cluster/    topology, jump-hash sharding, broadcast, node-to-node client
    models/     holder → index → frame → view schema hierarchy
    storage/    fragment (snapshot+oplog), roaring bitmaps, caches, attrs
    ops/        device kernel layer: packed bitmaps, XLA kernels, the
                Pallas sparse-upload densify
    parallel/   mesh construction, the device program catalogue, HBM residency
    utils/      time quantum engine, stats, config, iterators
"""

__version__ = "0.1.0"

# SliceWidth is the number of columns in a slice (reference: fragment.go:47).
SLICE_WIDTH = 1 << 20
