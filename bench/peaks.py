"""The benchmark's table of chip peaks (``bench/peaks.json``), keyed by
the exact ``device_kind`` that JAX reports. This is the only source of
peaks for the benchmark's metrics; a kind missing from it is an error."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device's kind has no row in the peak table."""

    def __str__(self) -> str:
        return str(self.args[0])


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    """Peaks of one chip of ``device_kind``, with the table's source."""
    data = json.loads(table.read_text())
    row = data["devices"].get(device_kind)
    if row is None:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {table.name} "
            f"(known: {sorted(data['devices'])})"
        )
    return dict(row, source=data["source"])
