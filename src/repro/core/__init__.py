"""Core bitmap-index library: the paper's contribution.

EWAH word-aligned compression, WAH baseline, k-of-N encoding with alphabetic
(Algorithm 2) and Gray-code allocation, fact-table sorting (lexicographic,
Gray-code, random-sort grouping, block-wise), index construction (Algorithm 3
semantics) and the query engine.
"""
from .bitpack import pack_bits, unpack_bits, pack_matrix
from .ewah import EWAH, binary_op, and_many, or_many
from .containers import (CHUNK_BITS, Containers, T_ARRAY, T_DENSE, T_EMPTY,
                         T_FULL, T_RUN)
from .wah import WAH
from .encoding import ColumnEncoder, bitmaps_needed, choose_k, unrank_lex, revolving_door
from .layout import (ADVISOR_VERSION, LayoutDecision, LayoutStats,
                     advise_order, remap_from_counts, validate_remap)
from .sorting import (
    SortStats, lex_sort, gray_sort, lex_sort_bits, random_sort,
    random_shuffle, block_sort, external_merge_sort_perm,
    external_sorted_chunks, order_columns, order_columns_freq_aware,
)
from .index import (BitmapIndex, ColumnIndex, IndexBuilder, concat_bitmaps,
                    validate_partition_rows)
from .store import (StoreCorruptError, StoreError, StoreVersionError,
                    StoreWriter, load, load_sharded, manifest_meta, save,
                    save_sharded, write_shard_file)
from .expr import (And, Col, Const, Eq, Expr, In, Not, Or, Range,
                   canonical_key, col, from_wire, to_wire)
from .planner import explain, plan
from .statement import QueryBatch, execute, execute_rows
from . import statement
from .shard import ForkSafetyError, ShardedIndex, ShardProcessPool
from .wal import WAL, WALError, replay as wal_replay
from .ingest import Compactor, DeltaIndex, LiveIndex
from .dataset import Dataset, Query
from . import query
from . import synth

__all__ = [
    "pack_bits", "unpack_bits", "pack_matrix",
    "EWAH", "binary_op", "and_many", "or_many", "WAH",
    "Containers", "CHUNK_BITS",
    "T_EMPTY", "T_FULL", "T_ARRAY", "T_DENSE", "T_RUN",
    "ColumnEncoder", "bitmaps_needed", "choose_k", "unrank_lex", "revolving_door",
    "ADVISOR_VERSION", "LayoutDecision", "LayoutStats", "advise_order",
    "remap_from_counts", "validate_remap",
    "SortStats", "lex_sort", "gray_sort", "lex_sort_bits", "random_sort",
    "random_shuffle", "block_sort", "external_merge_sort_perm",
    "external_sorted_chunks", "order_columns", "order_columns_freq_aware",
    "BitmapIndex", "ColumnIndex", "IndexBuilder", "ShardedIndex",
    "ShardProcessPool", "ForkSafetyError",
    "concat_bitmaps", "validate_partition_rows",
    "StoreError", "StoreVersionError", "StoreCorruptError", "StoreWriter",
    "save", "load", "save_sharded", "load_sharded", "write_shard_file",
    "manifest_meta",
    "Expr", "Col", "col", "Eq", "In", "Range", "And", "Or", "Not", "Const",
    "canonical_key", "from_wire", "to_wire",
    "plan", "explain", "execute", "execute_rows", "QueryBatch", "statement",
    "WAL", "WALError", "wal_replay",
    "LiveIndex", "DeltaIndex", "Compactor",
    "Dataset", "Query",
    "query", "synth",
]
