"""The names the measurement reads: device programs, host-phase spans.

A device trace lists one ``XLA Modules`` event per execution of a jitted
program, named ``jit_<function>``; a metric that sums a program's device
seconds finds it by that name.  So every jitted program on a benchmark
cell's path is a function with a name of its own (the ``def`` that
``jax.jit`` sees — no wrapper), declared here once;
``tests/test_obs_names.py`` lowers each and holds it to its constant.

The span names beside them are the host phases inside an op, stage or
command where the device waits (category ``host``) and the dispatch of a
fused device loop up to the pull that ends it (category ``engine``).
The span sites use these constants, ``benchmark/layer_metrics/*.json``
quote them, ``doc/observability.md`` lists them with their attrs.
"""

# -- device programs ----------------------------------------------------------
# apps/invertedindex.py
INVINDEX_EXTRACT = "jit_invindex_extract"           # the mesh map stage
INVINDEX_COLLISIONS = "jit_invindex_collision_count"
# parallel/group.py
CONVERT_SORT = "jit_convert_sort"                   # per-shard sort + boundaries
CONVERT_LAYOUT = "jit_convert_layout"               # grouped layout at gcap
REDUCE_SEGMENTS = "jit_reduce_segments"
GROUP_FIRST = "jit_group_first"
SORT_MULTIVALUES = "jit_sort_multivalues"
SORT_ROWS = "jit_sort_rows"
SORT_INTERNED = "jit_sort_interned"
JOIN_ROWS = "jit_join_rows"                         # both sides' keys in one sort
JOIN_TAKE = "jit_join_take"                         # the joined rows' values
COMBINE = "jit_combine"                             # compress of few keys: no sort
# parallel/shuffle.py
SHUFFLE_PHASE1 = "jit_shuffle_phase1"
SHUFFLE_PHASE2 = "jit_shuffle_phase2"
SHUFFLE_PHASE2_WIRE = "jit_shuffle_phase2_wire"
# apps/terasort.py
TERASORT_SAMPLE_KEYS = "jit_terasort_sample"        # a stride of each shard's keys
TERASORT_JOIN_RECORDS = "jit_join_records"          # a window of a shard's rows
#                                                     as the part file's bytes
# parallel/staging.py
STAGE_RANK_GRAPH = "jit_stage_rank_graph"           # vertex table + edge ranks
STAGE_TRIM_VERTS = "jit_stage_trim_verts"
# parallel/sharded.py
PLACE_ROWS = "jit_place_rows"                       # device rows → shard blocks
# parallel/devkernels.py
CONCAT_ROWS = "jit_concat_rows"                     # append, per shard: a copy
TAKE_ROWS = "jit_take_rows"                         # a scan's kept rows, by index
LEVEL_ROWS = "jit_level_rows"                       # excess rows → short shards
REMAP_IDS = "jit_remap_ids"
# models/
CC_LOOP = "jit_cc_loop"
PAGERANK_LOOP = "jit_pagerank_loop"
RMAT_EDGES = "jit_rmat_edges"
RMAT_EDGE_ROWS = "jit_rmat_edge_rows"               # [m, 2] keys + NULL values
TRI_ORIENT = "jit_tri_orient"                       # keys, degrees, neighbour lists
TRI_TILES = "jit_tri_tiles"                         # a table of tiles, once a job
TRI_WEDGES = "jit_tri_wedges"                       # one batch, of tiles or of wedge
#                                                     indices: its wedges, join, compact
TRI_APPEND = "jit_tri_append"                       # a batch's hits into the buffer
TRI_GROW = "jit_tri_grow"
TRI_ROWS = "jit_tri_rows"                           # (centre, u, w) id rows
LUBY_LOOP = "jit_luby_loop"
SSSP_LOOP = "jit_sssp_loop"
SSSP_WEIGHTS = "jit_sssp_weights"                   # int32 where exact

PROGRAMS = (
    INVINDEX_EXTRACT, INVINDEX_COLLISIONS, CONVERT_SORT, CONVERT_LAYOUT,
    REDUCE_SEGMENTS, GROUP_FIRST, SORT_MULTIVALUES, SORT_ROWS, SORT_INTERNED,
    JOIN_ROWS, JOIN_TAKE, COMBINE, TAKE_ROWS,
    SHUFFLE_PHASE1, SHUFFLE_PHASE2, SHUFFLE_PHASE2_WIRE, STAGE_RANK_GRAPH,
    STAGE_TRIM_VERTS, PLACE_ROWS, CONCAT_ROWS, LEVEL_ROWS, REMAP_IDS,
    CC_LOOP, PAGERANK_LOOP, RMAT_EDGES, RMAT_EDGE_ROWS,
    TRI_ORIENT, TRI_TILES, TRI_WEDGES, TRI_APPEND, TRI_GROW, TRI_ROWS,
    LUBY_LOOP, SSSP_LOOP, SSSP_WEIGHTS, TERASORT_SAMPLE_KEYS,
    TERASORT_JOIN_RECORDS,
)

# parallel/devkernels.py's two generic mappers run one program per kernel
# body: ``jit_kv_map_<body>`` / ``jit_kmv_map_<body>``; the scan of a
# resident table (``skv_scan``: packed by a sort) ``jit_kv_scan_<body>``
KV_MAP_PREFIX = "jit_kv_map_"
KMV_MAP_PREFIX = "jit_kmv_map_"
KV_SCAN_PREFIX = "jit_kv_scan_"
# the combiner over a deferred scan (``skv_keep``) applies the scan's body
# inside its own program: ``jit_combine_<body>``
COMBINE_PREFIX = "jit_combine_"
PROGRAM_PREFIXES = (KV_MAP_PREFIX, KMV_MAP_PREFIX, KV_SCAN_PREFIX,
                    COMBINE_PREFIX)


def declared_program(module: str) -> bool:
    """Whether a trace's module name is one declared here."""
    return module in PROGRAMS or module.startswith(PROGRAM_PREFIXES)


# -- steps inside the programs ------------------------------------------------
# A step is a ``jax.named_scope`` inside a program: one component of the
# ``op_name`` path every HLO instruction carries into the profiler's trace
# (``jit(cc_loop)/while/body/shard_map/segment_min_dst/gather_src/gather``),
# which is how a device second gets a name below the program's
# (benchmark/xsteps.py reads the table out of the trace's own HLO).  Every
# operation of a declared program lies under one of its steps; where steps
# nest, the innermost is the operation's.  A step is lower_snake, unique
# inside its program, and none of JAX's own path words (STEP_RESERVED: a
# loop's ``body`` would claim every operation of every loop).  A rename
# here is a rename in ``benchmark/layer_metrics/*.json`` and in
# ``doc/observability.md``; tests/test_obs_names.py holds source, lowered
# programs and this table to one another.
STEP_RESERVED = ("while", "body", "cond", "shard_map", "scan", "pjit",
                 "branch", "checkpoint", "remat", "vmap", "jvp", "transpose")
STEPS = {
    INVINDEX_EXTRACT: ("mark", "compact", "gather", "hash", "long_tail",
                       "pack", "collisions", "stats"),
    INVINDEX_COLLISIONS: ("collisions",),
    CONVERT_SORT: ("sort", "take", "boundary"),
    CONVERT_LAYOUT: ("layout", "segment_ids", "flagged_rows_first",
                     "group_sizes", "unique_keys", "group_offsets",
                     "largest_group"),
    REDUCE_SEGMENTS: ("segment_ids", "reduce"),
    GROUP_FIRST: ("gather",),
    SORT_MULTIVALUES: ("segment_ids", "sort", "take"),
    # ops/sort.sort_carrying's two steps (the sort; the take of the words
    # that did not ride, by the sorted row index), under the words around
    SORT_ROWS: ("keys", "sort", "take"),
    SORT_INTERNED: ("rank", "sort", "take"),
    JOIN_ROWS: ("sort_sides", "partners", "joined_rows_first"),
    JOIN_TAKE: ("positions", "take"),
    # parallel/group.combine_sharded: the rows that count, the shard's
    # distinct keys by masked minima (a bounded loop), one masked reduction
    # a key found, the largest group's rows
    COMBINE: ("distinct_keys", "fold", "largest_group"),
    TAKE_ROWS: ("take",),
    SHUFFLE_PHASE1: ("dest", "dest_sort", "dest_counts", "wire_stats"),
    # the exchange's helpers carry them (parallel/shuffle._send_windows,
    # _exchange_blocks, _place_blocks): a round's send block cut from the
    # dest-sorted shard, the collective (the counts' too), the received
    # blocks placed in the packed output
    SHUFFLE_PHASE2: ("windows", "exchange", "unpack"),
    SHUFFLE_PHASE2_WIRE: ("windows", "exchange", "unpack"),
    TERASORT_SAMPLE_KEYS: ("sample",),
    # the key words byte-swapped, the value words shifted in behind them:
    # elementwise over one window (core/column.fixed_record_words)
    TERASORT_JOIN_RECORDS: ("join",),
    STAGE_RANK_GRAPH: ("endpoints", "sort", "rank", "table", "return"),
    STAGE_TRIM_VERTS: ("trim",),
    PLACE_ROWS: ("window",),
    CONCAT_ROWS: ("append",),
    LEVEL_ROWS: ("level",),
    REMAP_IDS: ("remap",),
    CC_LOOP: ("init", "segment_min_dst", "gather_src", "segment_min_src",
              "gather_dst", "pointer_jump", "merge", "changed"),
    PAGERANK_LOOP: ("out_degrees", "gather_ranks", "gather_inv_outdeg",
                    "scatter_add", "merge", "normalise", "delta"),
    RMAT_EDGES: ("generate",),
    RMAT_EDGE_ROWS: ("rows",),
    TRI_ORIENT: ("ids", "edge_keys", "degrees", "orient", "wedge_offsets",
                 "tile_offsets", "counts"),
    TRI_TILES: ("tiles",),
    # an index batch runs expand + partner, a tile batch blocks + pairs
    TRI_WEDGES: ("expand", "partner", "blocks", "pairs", "join",
                 "join_scan", "compact"),
    TRI_APPEND: ("append",),
    TRI_GROW: ("grow",),
    TRI_ROWS: ("rows",),
    LUBY_LOOP: ("orient", "undecided", "select", "gather", "prefix",
                "merge"),
    SSSP_LOOP: ("prologue", "init", "gather", "relax", "select", "merge",
                "update"),
    SSSP_WEIGHTS: ("weights",),
    # the generic mappers, by prefix: the kernel body's own operations,
    # then what brings the rows it keeps to the front (a prefix sum and a
    # scatter in ``jit_kv_map_*`` / ``jit_kmv_map_*``, a one-operand sort
    # in ``jit_kv_scan_*``; a map that keeps every row has no ``pack``)
    KV_MAP_PREFIX: ("kernel", "pack"),
    KMV_MAP_PREFIX: ("kernel", "pack"),
    KV_SCAN_PREFIX: ("kernel", "pack"),
    COMBINE_PREFIX: ("distinct_keys", "fold", "largest_group", "kernel"),
}


def steps_of(module: str) -> tuple:
    """The steps declared for a trace's module name; none for a program
    that is not declared."""
    if module in STEPS:
        return STEPS[module]
    for prefix in PROGRAM_PREFIXES:
        if module.startswith(prefix):
            return STEPS[prefix]
    return ()


# -- span categories ----------------------------------------------------------
HOST = "host"           # host work inside an op, stage or command
ENGINE = "engine"       # a device loop, from dispatch to the pull that ends it
ENTRY = "entry"         # one root span a job: the call into an entry point

# -- entry-point spans (cat ENTRY) --------------------------------------------
# every span says cpu_s / off_cpu_s; these also proc_cpu_s, sys_cpu_s,
# vol_switches, invol_switches and the four jit_* deltas, zero or not
INVINDEX_RUN = "invindex.run"       # apps/invertedindex.InvertedIndex.run
OINK_SCRIPT = "oink.script"         # oink/script: the outermost script run
TERASORT_RUN = "terasort.run"       # apps/terasort.TeraSort.run
TPCH_Q3 = "tpch.q3"                 # apps/tpch.q3, inside the oink.script root
TPCH_Q1 = "tpch.q1"                 # apps/tpch.q1, likewise

# -- host-phase spans (cat HOST unless said) ----------------------------------
# parallel/shuffle.aggregate_kv, before the exchange / the one-chip early-out
AGGREGATE_ONE_FRAME = "aggregate.one_frame"     # rows, frames, cap,
#                                                 to_host_bytes, to_device_bytes
AGGREGATE_INTERN = "aggregate.intern"           # rows
AGGREGATE_SHARD = "aggregate.shard"             # rows, bytes
# parallel/group.convert_sharded: the pull between sort and layout
CONVERT_COUNT_SYNC = "convert.count_sync"       # groups
# parallel/group.combine_sharded: the pull of the shards' distinct-key counts
COMBINE_COUNT_SYNC = "combine.count_sync"       # groups
# oink/commands/rmat.py
RMAT_GENERATE = "rmat.generate"                 # rows, d2h_bytes
# oink/objects.py
OINK_INPUT = "oink.input"                       # source, rows, bytes
OINK_OUTPUT = "oink.output"                     # path, rows, bytes,
#                                                 block_rows (rows whose
#                                                 lines were formatted from
#                                                 columns a block at a time,
#                                                 core/column.format_rows;
#                                                 0: a printer call a row),
#                                                 native (1: the native
#                                                 formatter wrote them)
# oink/commands/{cc,pagerank}.py
CC_STAGE = "cc.stage"                           # n, edges, on_device (1:
#                                                 ranked by stage_graph, 0: on
#                                                 the host), shards (the
#                                                 mesh's size, 1 without a
#                                                 mesh), ATTR_EDGE_ROWS;
#                                                 pagerank.stage too
CC_EMIT = "cc.emit"                             # n
CC_ENGINE = "cc.loop"                           # cat ENGINE: iters, n, edges,
#                                                 ATTR_EDGE_ROWS,
#                                                 shards, allreduce_bytes (the
#                                                 replicated [n] vector merged
#                                                 over the mesh: n * 4 * the
#                                                 all-reduces an iteration *
#                                                 iters; 0 on one device)
PAGERANK_STAGE = "pagerank.stage"
PAGERANK_EMIT = "pagerank.emit"
PAGERANK_ENGINE = "pagerank.loop"               # cat ENGINE, as cc.loop
# oink/commands/{tri,luby,sssp}.py
TRI_STAGE = "tri.stage"                         # n, edges
TRI_ENGINE = "tri.loop"                         # cat ENGINE: wedges, batches
#                                                 (executions of TRI_WEDGES, of
#                                                 either kind), triangles,
#                                                 edges, n, max_out_degree,
#                                                 and ATTR_TILES,
#                                                 ATTR_INDEX_WEDGES,
#                                                 ATTR_TILE_FILL below
TRI_EMIT = "tri.emit"                           # triangles
LUBY_STAGE = "luby.stage"                       # n, edges, ATTR_EDGE_ROWS
LUBY_ENGINE = "luby.loop"                       # cat ENGINE: iters, n, edges,
#                                                 rows, ATTR_EDGE_ROWS (the
#                                                 same number)
LUBY_EMIT = "luby.emit"                         # n
SSSP_STAGE = "sssp.stage"                       # n, edges, ATTR_EDGE_ROWS
SSSP_ENGINE = "sssp.loop"                       # cat ENGINE: iters, source,
#                                                 labeled, n, edges,
#                                                 ATTR_EDGE_ROWS
SSSP_EMIT = "sssp.emit"                         # n, source, rows,
#                                                 block_rows, native (as
#                                                 oink.output; 0 rows as
#                                                 blocks with no -o path)
# apps/invertedindex.py
MAP_PLAN = "map.plan"                           # files, bytes, rounds
MAP_PAD = "map.pad"                             # bytes, shard_bytes
MAP_COLLISIONS = "map.collisions"               # rows, rounds, shards
PARTS_PULL = "parts.pull"                       # groups, bytes
PARTS_WRITE = "parts.write"                     # groups, bytes, pieces
#                                                 (byte ranges gathered:
#                                                 2 a group + 1 a distinct
#                                                 url-file pair), recoded
#                                                 (urls not ASCII, spelt
#                                                 again as UTF-8)

# utils/io.word_ranges / parallel/ingest._intern_shard: the word map of a
# file map, by ranges (cat HOST; ``shard`` when the map runs shard by shard).
# The interns run on pool threads, one span a shard: they overlap one
# another and the next shard's tokenize, so a metric over them is a union
# (wall covered); the enclosing map_files span says ``intern_busy_s``, their
# thread-seconds
INGEST_TOKENIZE = "ingest.tokenize"             # shard, bytes, words
INGEST_INTERN = "ingest.intern"                 # shard, words, unique, added,
#                                                 checked, table_bytes
# oink/commands/wordfreq.py: gather(1) + sort_values + the first ntop rows
WORDFREQ_TOPN = "wordfreq.topn"                 # rows

# parallel/ingest.mesh_map_records: the fixed-width record map.  One plan
# span (the shards' blocks sized and allocated, every file's cut handed to
# the pool), then a read and an h2d span a shard (a shard's read waits for
# ITS files; every file is in flight from the plan on, so a later shard's
# read is mostly over when it opens)
INGEST_RECORDS_PLAN = "ingest.records.plan"     # shards, files, block_bytes
INGEST_RECORDS_READ = "ingest.records.read"     # shard, files, bytes
INGEST_RECORDS_H2D = "ingest.records.h2d"       # shard, bytes; the puts'
#                             dispatch, the host block let go; the last
#                             shard's also waits for every shard's blocks
# apps/terasort.py
TERASORT_SAMPLE = "terasort.sample"             # sampled, splitters,
#                                                 d2h_bytes
# the part writer: on a mesh a pull and a write span a WINDOW of a shard's
# rows (the device put the records together); the serial backend's frame
# is on the host, and its one pull and one write span the whole file
TERASORT_PULL = "terasort.pull"                 # shard, records (the
#                             window's rows below the shard's count),
#                             d2h_bytes (what crossed: whole windows; 0 on
#                             the serial backend); the exposed wait for
#                             the oldest window on its way, and the
#                             dispatch of the one WRITE_AHEAD behind it
TERASORT_WRITE = "terasort.write"               # shard, records, bytes,
#                             joined ("device", or "host" on the serial
#                             backend), windows (1; the serial backend's
#                             blocks): the write of those rows, and with a
#                             shard's last the file's close
# apps/tpch.py
TPCH_LOAD = "tpch.load"                         # table, files, rows, bytes
TPCH_SCAN = "tpch.scan"                         # table, and ATTR_ROWS_IN,
#                                                 ATTR_ROWS_OUT,
#                                                 ATTR_ROW_WORDS_IN below
TPCH_TOPN = "tpch.topn"                         # rows
TPCH_EMIT = "tpch.emit"                         # rows, bytes

# older spans that metrics quote by name
SHUFFLE_EXCHANGE = "shuffle.exchange"           # ..., recv_rows_max, _mean,
#                                                 cols_rode, cols_by_index,
#                                                 dest, phase1_built
SHUFFLE_COUNT_SYNC = "shuffle.count_sync"
OINK_RMAT = "oink.rmat"                         # rounds

SPANS = (
    AGGREGATE_ONE_FRAME, AGGREGATE_INTERN, AGGREGATE_SHARD,
    CONVERT_COUNT_SYNC, COMBINE_COUNT_SYNC, RMAT_GENERATE, OINK_INPUT, OINK_OUTPUT, CC_STAGE,
    CC_EMIT, CC_ENGINE, PAGERANK_STAGE, PAGERANK_EMIT, PAGERANK_ENGINE,
    MAP_PLAN, MAP_PAD, MAP_COLLISIONS, PARTS_PULL, PARTS_WRITE,
    SHUFFLE_EXCHANGE, SHUFFLE_COUNT_SYNC, OINK_RMAT,
    INGEST_TOKENIZE, INGEST_INTERN, WORDFREQ_TOPN,
    TRI_STAGE, TRI_ENGINE, TRI_EMIT, LUBY_STAGE, LUBY_ENGINE, LUBY_EMIT,
    SSSP_STAGE, SSSP_ENGINE, SSSP_EMIT,
    INVINDEX_RUN, OINK_SCRIPT,
    INGEST_RECORDS_PLAN, INGEST_RECORDS_READ, INGEST_RECORDS_H2D,
    TERASORT_RUN, TERASORT_SAMPLE, TERASORT_PULL, TERASORT_WRITE,
    TPCH_Q3, TPCH_Q1, TPCH_LOAD, TPCH_SCAN, TPCH_TOPN, TPCH_EMIT,
)

# -- attrs that metrics quote by name -----------------------------------------
# on the ``convert`` op span (core/mapreduce.convert): the rows grouped, the
# groups they fell into, and the rows of the largest group
CONVERT_SPAN = "convert"
ATTR_ROWS = "rows"
ATTR_GROUPS = "groups"
ATTR_GROUP_ROWS_MAX = "group_rows_max"
# on the ``compress`` op span of a mesh frame and a registered segment
# reduce (parallel/group.combine_sharded): the rows, the 32-bit words of a
# key and of a value, which road ran (``combined`` 1: the combiner folded
# the rows where they lay; 0: ``convert`` + ``reduce`` sorted them), and on
# the combiner's road the groups and the largest group's rows
COMPRESS_SPAN = "compress"
ATTR_VALUE_WORDS = "value_words"
ATTR_COMBINED = "combined"
# on the ``sort_keys`` / ``sort_values`` op spans of a mesh dataset
# (parallel/group.sort_sharded): the rows sorted, the 32-bit key operands
# of the sort, the carried words that rode it and those taken by the row
# index, and the bytes a row of the frame occupies in HBM
SORT_KEYS_SPAN = "sort_keys"
SORT_VALUES_SPAN = "sort_values"
ATTR_RECORDS = "records"
ATTR_KEY_WORDS = "key_words"
ATTR_RODE_WORDS = "rode_words"
ATTR_TAKEN_WORDS = "taken_words"
ATTR_HBM_ROW_BYTES = "hbm_row_bytes"
# on the ``join`` op span (core/mapreduce.join): the rows of the two sides,
# the probe rows that found a partner, and the four attrs of the sorts above
# (the key's operands, the payload words both sides' values share, the bytes
# a probe row occupies in HBM); its Counters deltas are ``join_in_bytes`` and
# ``join_out_bytes``
JOIN_SPAN = "join"
ATTR_PROBE_ROWS = "probe_rows"
ATTR_BUILD_ROWS = "build_rows"
ATTR_MATCHED_ROWS = "matched_rows"
# on the ``tpch.scan`` spans (apps/tpch.py): the table's rows, the rows the
# predicate kept, and the 32-bit words of a table row (key and value)
ATTR_ROWS_IN = "rows_in"
ATTR_ROWS_OUT = "rows_out"
ATTR_ROW_WORDS_IN = "row_words_in"
# on the ``tri.loop`` span (oink/commands/tri.py, from models/tri.Walk): the
# tiles the long out-lists were cut into, the wedges of the short lists
# (walked index by index), and the wedges that came from tiles over the
# rows the tile batches generated (0 where no tile batch ran)
ATTR_TILES = "tiles"
ATTR_INDEX_WEDGES = "index_wedges"
ATTR_TILE_FILL = "tile_fill"
# on the four graph loops' ``*.loop`` and ``*.stage`` spans: the rows the
# program iterates or ranks (the staged columns' length: on a mesh, shards
# times the per-shard capacity ``round_cap`` gave the frame), beside
# ``edges``, the valid ones: ``edges`` / ``edge_rows`` is how full a loop's
# rows are (a masked row is gathered and scattered like a real one)
ATTR_EDGES = "edges"
ATTR_EDGE_ROWS = "edge_rows"
# on every span (obs/tracer.Span): the thread's CPU seconds, and the rest of
# the span's wall (blocked on the device, a file, a lock, or descheduled)
ATTR_CPU_S = "cpu_s"
ATTR_OFF_CPU_S = "off_cpu_s"
# on ENTRY spans only: every thread's CPU seconds, the kernel's share of
# the calling thread's cpu_s, its context switches, and what JAX lowered
# and loaded under the span
ATTR_PROC_CPU_S = "proc_cpu_s"
ATTR_SYS_CPU_S = "sys_cpu_s"
ATTR_VOL_SWITCHES = "vol_switches"
ATTR_INVOL_SWITCHES = "invol_switches"
ATTR_JIT_LOWERINGS = "jit_lowerings"
ATTR_JIT_LOWER_S = "jit_lower_s"
ATTR_JIT_BACKEND_S = "jit_backend_s"
ATTR_JIT_CACHE_LOADS = "jit_cache_loads"
SPAN_ATTRS = (
    ATTR_ROWS, ATTR_GROUPS, ATTR_GROUP_ROWS_MAX, ATTR_VALUE_WORDS,
    ATTR_COMBINED, ATTR_RECORDS, ATTR_KEY_WORDS,
    ATTR_RODE_WORDS, ATTR_TAKEN_WORDS, ATTR_HBM_ROW_BYTES,
    ATTR_PROBE_ROWS, ATTR_BUILD_ROWS, ATTR_MATCHED_ROWS,
    ATTR_ROWS_IN, ATTR_ROWS_OUT, ATTR_ROW_WORDS_IN,
    ATTR_TILES, ATTR_INDEX_WEDGES, ATTR_TILE_FILL,
    ATTR_EDGES, ATTR_EDGE_ROWS,
    ATTR_CPU_S, ATTR_OFF_CPU_S,
    ATTR_PROC_CPU_S, ATTR_SYS_CPU_S, ATTR_VOL_SWITCHES, ATTR_INVOL_SWITCHES,
    ATTR_JIT_LOWERINGS, ATTR_JIT_LOWER_S, ATTR_JIT_BACKEND_S,
    ATTR_JIT_CACHE_LOADS,
)
