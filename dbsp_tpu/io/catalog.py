"""Catalog: named, schema'd registry of a circuit's input/output handles.

Reference: ``adapters/src/catalog.rs:15`` plus the serde bridge
(``DeCollectionHandle``, adapters/src/deinput.rs:128, and ``SerBatch``,
seroutput.rs:14): the untyped boundary where parsers push rows into typed
handles and encoders read batches out.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

from dbsp_tpu.operators.io_handles import InputHandle, OutputHandle
from dbsp_tpu.io.format import WeightedRow
from dbsp_tpu.zset.batch import ColumnBlock


@dataclasses.dataclass
class InputCollection:
    name: str
    handle: InputHandle
    dtypes: Tuple  # (key..., val...) column dtypes, parser order

    def push_rows(self, rows: Union[List[WeightedRow], ColumnBlock]) -> int:
        """The single entry of parsed rows into the handle: a POST's block
        (``Parser.take_columns``) or a transport's weighted row tuples."""
        self.handle.extend(rows)
        return len(rows)


@dataclasses.dataclass
class OutputCollection:
    name: str
    handle: OutputHandle
    dtypes: Tuple


class Catalog:
    def __init__(self):
        self.inputs: Dict[str, InputCollection] = {}
        self.outputs: Dict[str, OutputCollection] = {}

    def register_input(self, name: str, handle: InputHandle,
                       dtypes: Sequence) -> None:
        if name in self.inputs:
            raise ValueError(f"duplicate input {name}")
        self.inputs[name] = InputCollection(name, handle, tuple(dtypes))

    def register_output(self, name: str, handle: OutputHandle,
                        dtypes: Sequence) -> None:
        if name in self.outputs:
            raise ValueError(f"duplicate output {name}")
        self.outputs[name] = OutputCollection(name, handle, tuple(dtypes))

    def input(self, name: str) -> InputCollection:
        if name not in self.inputs:
            raise KeyError(
                f"unknown input collection {name!r}; have {sorted(self.inputs)}")
        return self.inputs[name]

    def output(self, name: str) -> OutputCollection:
        if name not in self.outputs:
            raise KeyError(
                f"unknown output collection {name!r}; have {sorted(self.outputs)}")
        return self.outputs[name]
