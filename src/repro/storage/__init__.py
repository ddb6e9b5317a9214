"""Compressed device-resident column store (paper §5-6; DESIGN.md §Storage)
plus its durability layer: CRC32C integrity manifests, verified reads, and
checksummed generation-stamped snapshots (§Durability)."""
from .columns import (  # noqa: F401
    DenseColumn,
    DeviceColumn,
    DictPackedColumn,
    PackedColumn,
)
from .integrity import (  # noqa: F401
    attach_manifest,
    build_manifest,
    column_digest,
    crc32c,
    crc32c_parts,
    decode_fresh,
    detach_manifest,
    encoded_parts,
    iter_columns,
)
from .policy import (  # noqa: F401
    build_device_column,
    choose_device_encoding,
    column_uniques,
    device_space_report,
    permute_column,
    resolve_device_encoding,
)
from .snapshot import (  # noqa: F401
    latest_generation,
    list_generations,
    load_column_arrays,
    restore_db,
    snapshot_db,
)
