"""Data formats: parsers (bytes -> weighted rows) and encoders (batches ->
bytes).

Reference: ``adapters/src/lib.rs:91-101`` (InputFormat/Parser/OutputFormat/
Encoder traits) and the CSV implementation (``adapters/src/format/csv.rs``).
JSON here is newline-delimited with explicit insert/delete envelopes, which
the reference gained later; CSV rows are inserts with an optional trailing
weight column.

A parser hands its rows out in two forms. ``take()`` gives weighted row
tuples (the transports and tests). ``take_columns()`` gives one
:class:`~dbsp_tpu.zset.batch.ColumnBlock` — a numpy array per schema column
and a weight vector, held to the columns' domain — which is what the HTTP
ingest route pushes into an input handle, so a POSTed body reaches
``Batch.from_block`` with no row tuple in between. The JSON parser reads
*regular* NDJSON (envelopes or bare arrays of plain numbers, the schema's
arity, spelled as ``json.dumps`` spells them by default or with compact
separators) a chunk of whole lines at a time, as text: the lines' shape is
checked with the digits taken out, the digits are parsed with the shape
taken out, and no Python object is made per record or per value. A chunk
holding anything else goes line by line through ``_parse_line``, which
defines what every record means and which errors it raises. The choice is
made per chunk on what the body holds; ``columnar`` / ``fallback`` count the
rows each way took.
"""

from __future__ import annotations

import csv
import io
import json
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from dbsp_tpu.zset.batch import Batch, ColumnBlock, Row, transposed

WeightedRow = Tuple[Row, int]

# Bytes of whole lines the JSON parser's bulk path takes at a time (a chunk
# runs on to the end of the line this many bytes in: ~2,400 NEXmark bids).
# The ingest handler shares the interpreter with the threads that answer
# /view reads, and a C call holds it from start to end: every call of the
# bulk path scans one chunk, and the slowest of them (the numeric parse)
# takes ~0.4 ms on 128 KiB where it would hold every reader off for 15 ms on
# a 2.5 MB body.
_BULK_BYTES = 1 << 17


class Parser:
    """Incremental parser: feed chunks, take parsed weighted rows."""

    # rows taken so far by the columnar bulk path / by the line parser
    columnar = 0
    fallback = 0

    def feed(self, chunk: bytes) -> None:
        raise NotImplementedError

    def take(self) -> List[WeightedRow]:
        raise NotImplementedError

    def take_columns(self) -> ColumnBlock:
        """Everything parsed since the last take, as one block of the
        schema's columns; raises ``ValueError`` for a value a column's
        dtype cannot hold or reserves (``ColumnBlock.from_parts``)."""
        raise NotImplementedError

    def eoi(self) -> None:
        """End of input: flush any buffered partial record."""


class _LineParser(Parser):
    def __init__(self, dtypes: Sequence):
        self.dtypes = tuple(dtypes)
        # one converter per column, fixed here and not asked per value
        self._convert = tuple(
            float if np.issubdtype(np.dtype(d), np.floating) else int
            for d in self.dtypes)
        self._buf = b""
        self._rows: List[WeightedRow] = []  # the line parser's open run
        # what take*() hands out, in arrival order: closed runs of the
        # line parser (lists of weighted rows) and the bulk path's chunks
        # ((per-column numpy arrays, weight array) pairs)
        self._parts: list = []

    def feed(self, chunk: bytes) -> None:
        data = self._buf + chunk if self._buf else chunk
        cut = data.rfind(b"\n") + 1
        self._buf = data[cut:]
        if cut:
            self._parse_text(data[:cut])

    def eoi(self) -> None:
        last, self._buf = self._buf, b""
        if last.strip():
            self._parse_text(last + b"\n")

    def take(self) -> List[WeightedRow]:
        rows: List[WeightedRow] = []
        for part in self._take_parts():
            if isinstance(part, list):
                rows.extend(part)
            else:
                cols, weights = part
                rows.extend(zip(zip(*(c.tolist() for c in cols)),
                                weights.tolist()))
        return rows

    def take_columns(self) -> ColumnBlock:
        return ColumnBlock.from_parts(
            [transposed(part) if isinstance(part, list) else part
             for part in self._take_parts()], self.dtypes)

    def _take_parts(self) -> list:
        self._close_run()
        parts, self._parts = self._parts, []
        return parts

    def _close_run(self) -> None:
        if self._rows:
            self._parts.append(self._rows)
            self._rows = []

    def _parse_text(self, text: bytes) -> None:
        """Whole lines, each ended by its newline: one record a line,
        blank lines skipped."""
        n0 = len(self._rows)
        for line in text.split(b"\n"):
            line = line.strip()
            if line:
                self._parse_line(line.decode())
        self.fallback += len(self._rows) - n0

    def _parse_line(self, line: str) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def _coerce(self, values: Sequence) -> Row:
        return tuple(f(v) for f, v in zip(self._convert, values))


class CsvParser(_LineParser):
    """One record per line; columns ordered (keys..., vals...[, weight])."""

    def _parse_line(self, line: str) -> None:
        fields = next(csv.reader([line]))
        n = len(self.dtypes)
        if len(fields) == n + 1:
            w = int(fields[n])
        elif len(fields) == n:
            w = 1
        else:
            raise ValueError(
                f"CSV record has {len(fields)} fields, schema has {n}")
        self._rows.append((self._coerce(fields[:n]), w))


_NEWLINE_TO_COMMA = bytes.maketrans(b"\n", b",")
# what a regular line's shape is rewritten to: the envelope's weight
_INSERT, _DELETE = b"1", b"2"


class JsonParser(_LineParser):
    """NDJSON with envelopes: {"insert": [..cols..]} or {"delete": [...]};
    a bare array is an insert."""

    def __init__(self, dtypes: Sequence):
        super().__init__(dtypes)
        self._floating = float in self._convert
        # the bulk path reads integers as int64: a uint64 column (and a
        # table of no columns) is the line parser's
        self._bulk_able = bool(self.dtypes) and np.dtype(np.uint64) not in \
            [np.dtype(d) for d in self.dtypes]
        # In a floating schema a number may hold ``e``, which the
        # envelopes' keys hold too: the keys are rewritten to two bytes no
        # JSON text holds before a chunk's numbers are told from its shape.
        insert, delete = ((b"\x01", b"\x02") if self._floating
                          else (b'"insert"', b'"delete"'))
        # the characters of a number, and those of a line's shape but for
        # the brackets (each set is deleted from a chunk, in turn)
        self._numeric = b"0123456789-" + b"+.eE" * self._floating
        self._shape_chars = b' {}":' + insert + delete
        # a regular line with its numbers' characters deleted, as
        # ``json.dumps`` spells it by default and with compact separators
        self._shapes = []
        for colon, comma in ((b": ", b", "), (b":", b",")):
            row = b"[" + comma * (len(self.dtypes) - 1) + b"]"
            self._shapes += [
                (b"{" + insert + colon + row + b"}\n", _INSERT),
                (b"{" + delete + colon + row + b"}\n", _DELETE),
                (row + b"\n", _INSERT)]

    def _parse_text(self, text: bytes) -> None:
        start = 0
        while start < len(text):
            end = text.find(b"\n", start + _BULK_BYTES) + 1 or len(text)
            chunk, start = text[start:end], end
            part = self._bulk(chunk) if self._bulk_able else None
            if part is None:
                super()._parse_text(chunk)
            else:
                self._close_run()
                self._parts.append(part)
                self.columnar += len(part[1])

    def _bulk(self, text: bytes) -> Optional[tuple]:
        """A chunk of whole lines as (per-column numpy arrays, weight
        array) if every line of it is a regular record: a list, or a dict
        with exactly one key, ``insert`` or ``delete``, whose value is a
        list; the schema's arity; in an integer column only JSON integers,
        in a floating one any JSON number; no space but json.dumps's after
        ``:`` and ``,``. ``None`` for any other chunk, bad JSON included:
        the line parser then says what it means."""
        if b"\r" in text:
            text = text.replace(b"\r\n", b"\n")
        # a space only straight after a colon or a comma (which of them,
        # the shape below says): "[1,2 3]" has the shape of "[1, 23]"
        if b" " in text and text.count(b" ") != \
                text.count(b", ") + text.count(b": "):
            return None
        if self._floating:
            if b"\x01" in text or b"\x02" in text:
                return None
            text = text.replace(b'"insert"', b"\x01") \
                       .replace(b'"delete"', b"\x02")
        # the lines' shape: with the numbers' characters gone every regular
        # line is one of six fixed spellings, each rewritten to its weight
        shape = text.translate(None, self._numeric)
        for spelled, mark in self._shapes:
            shape = shape.replace(spelled, mark)
        n = len(shape)  # every mark was a line
        if shape.count(_INSERT) + shape.count(_DELETE) != n:
            return None
        # the numbers: with the shape gone but for the brackets a regular
        # chunk reads "[n,n],[n,n],"; a numeric character outside a line's
        # brackets (inside its key, say) would leave "]5,[" somewhere
        numbers = text.translate(_NEWLINE_TO_COMMA, self._shape_chars) \
                      .replace(b"],[", b",")
        if numbers.rfind(b"[") != 0 or numbers.find(b"]") != len(numbers) - 2:
            return None
        numbers = numbers[1:-2]
        cols = (self._floating_cols if self._floating
                else self._integer_cols)(numbers, n)
        if cols is None:
            return None
        weights = 99 - 2 * np.frombuffer(shape, np.uint8).astype(np.int64)
        return cols, weights

    def _integer_cols(self, numbers: bytes, n: int) -> Optional[list]:
        """``b"t,t,...,t"``, ``n`` rows of tokens made of digits and ``-``
        -> int64 columns, if every token is a JSON integer of at most 18
        digits (so that it fits); else ``None``."""
        text = np.frombuffer(numbers + b",", np.uint8)
        ends = np.flatnonzero(text == 0x2C)  # ,
        starts = np.empty_like(ends)
        starts[0], starts[1:] = 0, ends[:-1] + 1
        minus = text[starts] == 0x2D  # -
        first = starts + minus
        digits = ends - first
        if digits.min() < 1 or digits.max() > 18 or \
                ((text[first] == 0x30) & (digits > 1)).any() or \
                np.count_nonzero(text == 0x2D) != np.count_nonzero(minus):
            return None  # an empty token, a long one, 007, 1-2
        return list(np.fromstring(numbers, np.int64, sep=",")
                    .reshape(n, len(self.dtypes)).T)

    def _floating_cols(self, numbers: bytes, n: int) -> Optional[list]:
        """The same tokens, of a number's characters, through the JSON
        decoder as one flat array: int64 columns where the schema's are
        integer (``None`` if a token there is no ``int``), float64 where
        floating (``float(v)`` of each)."""
        try:
            flat = json.loads(b"[" + numbers + b"]")
        except ValueError:
            return None
        cols = []
        for j, convert in enumerate(self._convert):
            try:  # (an integer column's dtype is numpy's to choose)
                col = np.array(flat[j::len(self._convert)],
                               dtype=np.float64 if convert is float else None)
            except OverflowError:
                return None
            if convert is int and col.dtype != np.int64:
                return None
            cols.append(col)
        return cols

    def _parse_line(self, line: str) -> None:
        obj = json.loads(line)
        if isinstance(obj, dict):
            if "insert" in obj:
                row, w = obj["insert"], 1
            elif "delete" in obj:
                row, w = obj["delete"], -1
            else:
                raise ValueError(f"JSON record needs insert/delete: {line}")
        else:
            row, w = obj, 1
        if len(row) != len(self.dtypes):
            raise ValueError(
                f"JSON record has {len(row)} fields, schema has "
                f"{len(self.dtypes)}")
        # coerce to schema dtypes NOW so type errors surface at the parse
        # boundary (HTTP 400 / endpoint error), not inside the circuit
        # thread; take_columns() holds the values to their columns' domain
        # at the same boundary
        self._rows.append((self._coerce(row), w))


class Encoder:
    def encode(self, batch: Batch) -> bytes:
        raise NotImplementedError


class CsvEncoder(Encoder):
    def encode(self, batch: Batch) -> bytes:
        out = io.StringIO()
        writer = csv.writer(out, lineterminator="\n")
        for row, w in sorted(batch.to_dict().items()):
            writer.writerow([*row, w])
        return out.getvalue().encode()


class JsonEncoder(Encoder):
    def encode(self, batch: Batch) -> bytes:
        lines = []
        for row, w in sorted(batch.to_dict().items()):
            env = "insert" if w > 0 else "delete"
            for _ in range(abs(w)):
                lines.append(json.dumps({env: list(row)}))
        return ("\n".join(lines) + "\n").encode() if lines else b""


INPUT_FORMATS = {"csv": CsvParser, "json": JsonParser}
OUTPUT_FORMATS = {"csv": CsvEncoder, "json": JsonEncoder}
