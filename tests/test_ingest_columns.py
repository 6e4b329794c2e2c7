"""A pushed body becomes columns, not row tuples (ISSUE 31): the JSON
parser's bulk path against the line parser that defines every record's
meaning, the input handle's block buffer, and the served ingest route."""

import json
import time
import urllib.error
import urllib.request
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from dbsp_tpu.circuit import Runtime
from dbsp_tpu.io import Catalog, CircuitServer, Controller, ControllerConfig
from dbsp_tpu.io import format as fmt
from dbsp_tpu.io.format import CsvParser, JsonParser
from dbsp_tpu.obs.tracing import SpanRecorder
from dbsp_tpu.operators import add_input_zset
from dbsp_tpu.zset.batch import Batch, ColumnBlock

I64_I32 = (jnp.int64, jnp.int32)
I64_F64 = (jnp.int64, jnp.float64)
I32_MAX = 2 ** 31 - 1


def _lines(n, start=0):
    return b"".join(b'{"insert": [%d, %d]}\n' % (k, k % 97)
                    for k in range(start, start + n))


def _lines_of_the_first_chunk():
    body = _lines(20000)
    return body[:body.find(b"\n", fmt._BULK_BYTES) + 1].count(b"\n")


# the bulk path takes whole lines, _BULK_BYTES and the rest of a line at a
# time: of _lines()'s, this many first
CHUNK = _lines_of_the_first_chunk()


# name -> (dtypes, the body as the chunks feed() gets, (columnar, fallback)
# rows the bulk parser is to count, None = not asserted)
BODIES = {
    "inserts": (I64_I32, [_lines(50)], (50, 0)),
    "inserts_deletes_bare_in_both_spellings": (
        I64_I32,
        [b'{"insert": [1, 10]}\n{"delete": [1, 10]}\n[2, 5]\n'
         b'{"delete":[-3,-7]}\n[4,4]\n{"insert":[0,-0]}\n'], (6, 0)),
    "crlf_no_last_newline": (
        I64_I32, [b'{"insert": [1, 10]}\r\n[2, 5]\r\n{"delete": [9, 9]}'],
        (3, 0)),
    # (regular records, but not as json.dumps spells them: the line parser)
    "blank_lines_padding_spaces": (
        I64_I32,
        [b'\n  {"insert": [1, 10]}  \r\n\r\n\t[2, 5]\n\n{"delete": [9, 9]}'],
        (1, 2)),  # (the last line, alone at end of input, is regular)
    "uncommon_spacing": (
        I64_I32, [b'[1 , 2]\n{"insert" : [3,4]}\n{ "delete": [5, 6] }\n'],
        (0, 3)),
    "split_inside_a_line": (
        I64_I32, [b'{"insert": [1, 10]}\n{"ins', b'ert": [2, 20]}\n[3, 30]\n'],
        (3, 0)),
    "split_inside_a_number": (
        I64_I32, [b'[1, 10]\n[22', b'2, 3', b'0]\n[3, 30]'], (3, 0)),
    "numeric_strings": (I64_I32, [b'{"insert": ["7", "1"]}\n[8, 2]\n'],
                        (0, 2)),
    "float_in_integer_column": (I64_I32, [b'[1, 2.0]\n[2, 3.9]\n'], (0, 2)),
    "exponent_in_integer_column": (I64_I32, [b'[1e2, 3]\n'], (0, 1)),
    "boolean_in_integer_column": (I64_I32, [b'[1, true]\n[false, 3]\n'],
                                  (0, 2)),
    "floating_column": (
        I64_F64,
        [b'[1, 2.5]\n{"insert": [2, 3]}\n{"delete": [3, -1e-3]}\n[4, 1e300]\n'
         b'{"delete":[5,-1.5E+3]}\n[-6, -0.0]\n'], (6, 0)),
    "float32_column": ((jnp.int32, jnp.float32), [b'[1, 0.1]\n[2, 7]\n'],
                       (2, 0)),
    "float_in_the_integer_column_of_a_floating_schema": (
        I64_F64, [b'[1.0, 2.5]\n[2, 3.5]\n'], (0, 2)),
    "past_int64_in_the_integer_column_of_a_floating_schema": (
        I64_F64, [b'[%d, 2.5]\n' % 2 ** 63], (0, 1)),
    "string_in_floating_column": (I64_F64, [b'[1, "2.5"]\n'], (0, 1)),
    "floats_json_does_not_know": (I64_F64, [b'[1, 1.]\n'], None),
    "a_float_without_its_zero": (I64_F64, [b'[1, .5]\n'], None),
    "an_exponent_without_digits": (I64_F64, [b'[1, 1e]\n'], None),
    "infinity_in_floating_column": (I64_F64, [b'[1, -Infinity]\n'], (0, 1)),
    "the_key_marks_as_bytes_in_a_floating_schema": (
        I64_F64, [b'{"\x01": [1, 2.5]}\n'], None),
    "an_e_in_the_key_of_a_floating_schema": (
        I64_F64, [b'{"inseret": [1, 2.5]}\n'], None),
    "uint64_column": ((jnp.uint64, jnp.int32),
                      [b'[%d, 1]\n[3, 4]\n' % (2 ** 63 + 5)], (0, 2)),
    "negative_in_an_unsigned_column": ((jnp.uint32, jnp.int32),
                                       [b'[-1, 1]\n'], (1, 0)),
    "wrong_arity": (I64_I32, [b'[1, 2]\n[1, 2, 3]\n'], None),
    "one_column_short": (I64_I32, [b'[1, 2]\n[1]\n'], None),
    "bad_json": (I64_I32, [b'[1, 2]\n{"insert": [1, 2}\n[3, 4]\n'], None),
    "not_utf8": (I64_I32, [b'[1, 2]\n[1, \xff]\n'], None),
    "neither_key": (I64_I32, [b'{"upsert": [1, 2]}\n'], None),
    "both_keys_insert_wins": (
        I64_I32, [b'{"delete": [1, 2], "insert": [3, 4]}\n'], (0, 1)),
    # (the decoder keeps the last of a repeated key)
    "a_key_twice": (I64_I32, [b'{"insert": [1, 2], "insert": [3, 4]}\n'],
                    (0, 1)),
    "nested_list": (I64_I32, [b'[1, [2]]\n'], None),
    "null_value": (I64_I32, [b'[1, null]\n'], None),
    "envelope_of_a_string": (I64_I32, [b'{"insert": "ab"}\n'], None),
    # numbers' characters where no number is
    "a_digit_inside_the_key": (I64_I32, [b'[1, 2]\n{"ins5ert": [3, 4]}\n'],
                               None),
    "a_digit_before_the_envelope": (I64_I32, [b'5{"insert": [3, 4]}\n'],
                                    None),
    "a_digit_after_the_bracket": (I64_I32, [b'[1, 2]\n[3, 4]5\n'], None),
    "a_minus_after_the_envelope": (I64_I32, [b'{"insert": [3, 4]}-\n[1, 2]\n'],
                                   None),
    # tokens of digits and '-' that are no JSON integer
    "leading_zero": (I64_I32, [b'[1, 2]\n[01, 2]\n'], None),
    "negative_leading_zero": (I64_I32, [b'[-01, 2]\n'], None),
    "minus_alone": (I64_I32, [b'[-, 2]\n'], None),
    "minus_inside_a_number": (I64_I32, [b'[1-2, 3]\n'], None),
    "two_minuses": (I64_I32, [b'[--1, 3]\n'], None),
    "minus_after_a_number": (I64_I32, [b'[1-, 3]\n'], None),
    "empty_token": (I64_I32, [b'[, 3]\n'], None),
    "space_inside_a_number": (I64_I32, [b'[1 2, 3]\n'], None),
    "plus_sign": (I64_I32, [b'[+1, 3]\n'], None),
    "zeros": (I64_I32, [b'[0, -0]\n[10, 100]\n'], (2, 0)),
    "eighteen_digits": (I64_I32, [b'[%d, 1]\n[-%d, 1]\n'
                                  % (10 ** 18 - 1, 10 ** 18 - 1)], (2, 0)),
    # (regular by type: the domain is held where the rows become columns)
    "past_int32": (I64_I32, [b'[1, %d]\n' % (I32_MAX + 1)], (1, 0)),
    "below_int32": (I64_I32, [b'[1, %d]\n' % (-I32_MAX - 2)], (1, 0)),
    "sentinel_int32": (I64_I32, [b'[1, %d]\n' % I32_MAX], (1, 0)),
    # (19 digits may not fit int64: the line parser's arbitrary integers)
    "nineteen_digits": (I64_I32, [b'[%d, 1]\n' % 10 ** 18], (0, 1)),
    "past_int64": (I64_I32, [b'[%d, 1]\n' % 2 ** 63], (0, 1)),
    "far_past_int64": (I64_I32, [b'[-%d, 1]\n' % 2 ** 80], (0, 1)),
    "sentinel_int64": (I64_I32, [b'{"insert": [%d, 1]}\n' % (2 ** 63 - 1)],
                       (0, 1)),
    "past_float64_in_floating_column": (
        I64_F64, [b'[1, 1%s]\n' % (b"0" * 400)], None),
    "int32_minimum": (I64_I32, [b'[1, %d]\n' % (-I32_MAX - 1)], (1, 0)),
    # lines that are JSON only once joined
    "two_lines_one_array": (I64_I32, [b'[1,\n2]\n[3,4],[5,6]\n'], None),
    "two_records_on_a_line": (I64_I32, [b'[3,4],[5,6]\n'], None),
    "array_of_arrays_over_lines": (I64_I32, [b'[[1,2]\n[3,4]]\n[5,6],[7,8]\n'],
                                   None),
    "chunk_minus_one_lines": (I64_I32, [_lines(CHUNK - 1)], (CHUNK - 1, 0)),
    "chunk_lines": (I64_I32, [_lines(CHUNK)], (CHUNK, 0)),
    "chunk_plus_one_lines": (I64_I32, [_lines(CHUNK + 1)], (CHUNK + 1, 0)),
    "irregular_line_in_the_second_chunk_only": (
        I64_I32, [_lines(CHUNK) + b'["5", 6]\n' + _lines(9, CHUNK)],
        (CHUNK, 10)),
    "bad_line_in_the_third_chunk": (
        I64_I32, [_lines(2 * CHUNK + 5) + b'[1, 2\n' + _lines(5)], None),
    "empty_body": (I64_I32, [b""], (0, 0)),
    "newlines_only": (I64_I32, [b"\n\n"], (0, 0)),
}


def _outcome(parser, feeds, take):
    """("rows", multiset of weighted rows) or ("raises", exception type)."""
    try:
        for chunk in feeds:
            parser.feed(chunk)
        parser.eoi()
        got = take(parser)
    except Exception as e:  # noqa: BLE001 — the type IS the result
        return "raises", type(e)
    return "rows", Counter(got)


@pytest.mark.parametrize("name", sorted(BODIES))
def test_bulk_path_gives_what_the_line_parser_gives(name, monkeypatch):
    """The same weighted rows as a multiset from ``take()`` and from
    ``take_columns()``, or the same exception type from both parsers."""
    dtypes, feeds, counts = BODIES[name]
    bulk = JsonParser(dtypes)
    by_tuples = _outcome(bulk, feeds, lambda p: p.take())
    by_columns = _outcome(JsonParser(dtypes), feeds,
                          lambda p: p.take_columns().rows())
    if counts is not None:
        assert (bulk.columnar, bulk.fallback) == counts
    # the reference: every chunk refused by the bulk path
    monkeypatch.setattr(JsonParser, "_bulk", lambda self, text: None)
    line = JsonParser(dtypes)
    ref_tuples = _outcome(line, feeds, lambda p: p.take())
    assert line.columnar == 0
    assert by_tuples == ref_tuples
    ref_columns = _outcome(JsonParser(dtypes), feeds,
                           lambda p: p.take_columns().rows())
    assert by_columns == ref_columns
    if name in ("past_int32", "below_int32", "past_int64", "far_past_int64",
                "sentinel_int32", "sentinel_int64",
                "negative_in_an_unsigned_column",
                "past_int64_in_the_integer_column_of_a_floating_schema"):
        # tuples carry the value as they always did; columns refuse it
        assert by_tuples[0] == "rows"
        assert by_columns == ("raises", ValueError)
    elif by_tuples[0] == "rows" and jnp.float32 not in dtypes:
        # (a float32 column holds its values at its own precision)
        assert by_columns == by_tuples


_MUTANT_BYTES = b'0123456789-+.eE,:[]{}" \t\r\n"insertdl\x01\x02x'


def _regular_body(rng, floating):
    lines = []
    for _ in range(rng.randint(1, 4)):
        a = rng.choice([0, 1, -1, 7, 42, -300, 10 ** 17, I32_MAX, 2 ** 31,
                        -2 ** 31, 2 ** 63 - 1, 2 ** 63, 123456])
        b = (rng.choice([0, 2.5, -1e-3, 1e300, 3, -0.0, 1.5e+20]) if floating
             else rng.choice([0, 5, -9, 1000]))
        comma, colon = rng.choice([(", ", ": "), (",", ":")])
        row = "[%r%s%r]" % (a, comma, b)
        env = rng.choice(["insert", "delete", None])
        lines.append(row if env is None else '{"%s"%s%s}' % (env, colon, row))
    return ("\n".join(lines) + rng.choice(["\n", ""])).encode()


def _mutated(rng, body):
    body = bytearray(body)
    for _ in range(rng.choice([0, 1, 1, 1, 2, 3])):
        i, op = rng.randrange(len(body) + 1), rng.random()
        if op < 0.4:
            body.insert(i, rng.choice(_MUTANT_BYTES))
        elif i < len(body) and op < 0.7:
            del body[i]
        elif i < len(body):
            body[i] = rng.choice(_MUTANT_BYTES)
    return bytes(body)


@pytest.mark.parametrize("seed", range(8))
def test_no_mutation_of_a_regular_body_tells_the_two_parsers_apart(
        seed, monkeypatch):
    """1,000 bodies a seed: one to four regular lines with up to three
    bytes inserted, dropped or overwritten, fed whole or in two pieces, in
    chunks of a few bytes or of the real size; by ``repr`` so that nan
    equals nan and -0.0 differs from 0.0."""
    import random

    class LineParser(JsonParser):
        def _bulk(self, text):
            return None

    def outcome(parser, feeds, take):
        kind, got = _outcome(parser, feeds, take)
        return kind, (Counter(map(repr, got.elements())) if kind == "rows"
                      else got)

    rng, columnar, real_size = random.Random(seed), 0, fmt._BULK_BYTES
    for _ in range(1000):
        floating = rng.random() < 0.4
        dtypes = I64_F64 if floating else I64_I32
        monkeypatch.setattr(fmt, "_BULK_BYTES",
                            rng.choice([8, 32, real_size]))
        body = _mutated(rng, _regular_body(rng, floating))
        cut = rng.randrange(len(body) + 1)
        feeds = [body[:cut], body[cut:]] if rng.random() < 0.3 else [body]
        for take in (lambda p: p.take(), lambda p: p.take_columns().rows()):
            bulk = JsonParser(dtypes)
            assert outcome(bulk, feeds, take) == \
                outcome(LineParser(dtypes), feeds, take), (body, dtypes)
        columnar += bulk.columnar
    assert columnar > 300  # the bulk path did take its share


def test_take_keeps_arrival_order_and_python_scalars():
    p = JsonParser(I64_I32)
    p.feed(b'[1, 10]\n["2", 20]\n' + _lines(3) + b'[4, "40"]\n')
    rows = p.take()
    # (one chunk, irregular: all of it by the line parser)
    assert rows == [((1, 10), 1), ((2, 20), 1), ((0, 0), 1), ((1, 1), 1),
                    ((2, 2), 1), ((4, 40), 1)]
    p.feed(_lines(CHUNK) + b'["7", 7]\n')
    rows = p.take()
    assert rows[:2] == [((0, 0), 1), ((1, 1), 1)] and rows[-1] == ((7, 7), 1)
    assert all(type(v) is int for (row, w) in rows for v in (*row, w))
    assert p.take() == [] and len(p.take_columns()) == 0


def test_csv_rows_come_as_columns_too():
    p = CsvParser((jnp.int64, jnp.float64))
    p.feed(b"1,2.5\n2,3,4\n")
    block = p.take_columns()
    assert [c.dtype for c in block.cols] == [np.int64, np.float64]
    assert block.rows() == [((1, 2.5), 1), ((2, 3.0), 4)]
    assert (p.columnar, p.fallback) == (0, 2)
    p.feed(b"%d,1.0\n" % (2 ** 63 - 1))
    with pytest.raises(ValueError, match="sentinel"):
        p.take_columns()


def test_a_block_slices_like_the_rows_it_replaces():
    block = ColumnBlock.from_rows([((k, -k), 1 - 2 * (k % 2))
                                   for k in range(7)], I64_I32)
    assert len(block) == 7 and len(block[:3]) == 3
    assert block[: len(block) // 2].rows() == block.rows()[:3]
    assert ColumnBlock.concat([block[:3], block[3:]]).rows() == block.rows()
    assert [c.dtype for c in block.cols] == [np.int64, np.int32]
    with pytest.raises(TypeError):
        block[0]


# -- the input handle ---------------------------------------------------------


def _input(workers=1):
    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int32])
        return h, s.output()

    handle, (h, out) = Runtime.init_circuit(workers, build)
    return handle, h, out


def _rows(n, start=0, weight=1):
    return [((k, k % 5), weight) for k in range(start, start + n)]


def _tick_of_tuples(rows):
    handle, h, out = _input()
    h.extend(rows)
    handle.step()
    return out.to_dict()


def test_tuples_a_block_and_a_batch_fold_into_one_tick():
    a, b, c = _rows(40), _rows(40, 20, weight=2), _rows(30, 50, weight=-1)
    handle, h, out = _input()
    h.extend(a)
    h.push((1000, 1), 3)
    h.extend(ColumnBlock.from_rows(b, I64_I32))
    h.push_batch(Batch.from_tuples(c, [jnp.int64], [jnp.int32]))
    handle.step()
    assert out.to_dict() == _tick_of_tuples(a + [((1000, 1), 3)] + b + c)
    handle.step()
    assert out.to_dict() == {}  # drained: nothing comes twice


def test_two_posts_blocks_concatenate():
    a, b = _rows(100), _rows(100, 50)
    handle, h, out = _input()
    h.extend(ColumnBlock.from_rows(a, I64_I32))
    h.extend(ColumnBlock.from_rows(b, I64_I32))
    h.extend(ColumnBlock.from_rows([], I64_I32))  # an empty POST
    assert len(h._op._blocks) == 2
    handle.step()
    got = out.to_dict()
    assert got == _tick_of_tuples(a + b)
    assert got[(60, 0)] == 2 and got[(0, 0)] == 1


def test_a_block_pushed_while_eval_runs_lands_in_the_next_tick(monkeypatch):
    handle, h, out = _input()
    late = ColumnBlock.from_rows(_rows(5, 500), I64_I32)
    real, calls = Batch.from_block, {"n": 0}

    def from_block(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:  # the buffers are swapped out by now
            h.extend(late)
        return real(*a, **kw)

    monkeypatch.setattr(Batch, "from_block", staticmethod(from_block))
    h.extend(ColumnBlock.from_rows(_rows(8), I64_I32))
    handle.step()
    assert out.to_dict() == dict(_rows(8))
    handle.step()
    assert out.to_dict() == dict(_rows(5, 500))


def test_a_block_of_other_columns_is_refused():
    _, h, _ = _input()
    with pytest.raises(AssertionError):
        h.extend(ColumnBlock.from_rows(_rows(3), (jnp.int64, jnp.int64)))


def test_on_four_workers_the_sharded_batch_equals_the_tuple_path_s():
    rows = [((k * 7919 % 1000, k % 5), 1 + k % 3) for k in range(600)]

    def sharded(push):
        handle, h, out = _input(workers=4)
        push(h)
        prev = Runtime._swap(handle.runtime)
        try:
            return h._op.eval()
        finally:
            Runtime._swap(prev)

    by_tuples = sharded(lambda h: h.extend(rows))
    by_block = sharded(lambda h: (
        h.extend(ColumnBlock.from_rows(rows[:250], I64_I32)),
        h.extend(ColumnBlock.from_rows(rows[250:], I64_I32))))
    assert by_block.sharded and by_block.weights.shape[0] == 4
    assert by_block.weights.shape == by_tuples.weights.shape
    for got, want in zip((*by_block.cols, by_block.weights),
                         (*by_tuples.cols, by_tuples.weights)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(by_block.live_count()) == len({r for r, _ in rows})


# -- one program per table shape (ISSUE 35) -----------------------------------

I32_I64_I32 = (jnp.int32, jnp.int64, jnp.int32)


def _scattered(n, mod):
    return [((k * 7919 % mod, (k * 31 % 11) * 10 ** 10, -(k % 3)), 1 + k % 2)
            for k in range(n)]


# name -> (dtypes, key columns, weighted rows in arrival order)
BLOCKS = {
    # (more rows than one chunk of the accelerator's merge sort)
    "unsorted": (I32_I64_I32, 2, _scattered(2500, 10 ** 6)),
    "duplicates_that_net": (I32_I64_I32, 1, _scattered(300, 40)),
    "rows_that_net_to_zero": (
        I64_I32, 1, _rows(50) + _rows(20, 10, weight=-1) + _rows(50, 30, -1)
        + _rows(30, 50)),
    "all_net_to_zero": (I64_I32, 2, _rows(20) + _rows(20, weight=-1)),
    "nothing_to_pad": (I32_I64_I32, 3, _scattered(256, 10 ** 6)),
    "one_row": (I64_I32, 1, [((2 ** 40, -7), -2)]),
    "no_value_columns": ((jnp.int64, jnp.int64), 2, [
        ((k % 9, 2 ** 62 - k % 4), 1) for k in range(100)]),
}


@pytest.mark.parametrize("dispatch", ["native", "accelerator"])
@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_a_block_s_batch_is_from_columns_batch_bit_for_bit(name, dispatch,
                                                            request):
    if dispatch == "accelerator":
        request.getfixturevalue("accelerator_dispatch")
    dtypes, nk, rows = BLOCKS[name]
    block = ColumnBlock.from_rows(rows, dtypes)
    got = Batch.from_block(block, nk)
    want = Batch.from_columns(block.cols[:nk], block.cols[nk:], block.weights)
    assert (got.cap, got.runs) == (want.cap, want.runs) == \
        (got.cap, (got.cap,))
    assert (len(got.keys), len(got.vals)) == (nk, len(dtypes) - nk)
    for g, w in zip((*got.cols, got.weights), (*want.cols, want.weights)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    net = Counter()
    for row, w in rows:
        net[row] += w
    assert got.to_dict() == {r: w for r, w in net.items() if w}
    if name == "nothing_to_pad":
        assert got.cap == len(rows)


def _parsed(rows):
    p = JsonParser(I64_I32)
    p.feed(b"".join(b"[%d, %d]\n" % r for r, _ in rows))
    return p.take_columns()


@pytest.mark.parametrize("build", [
    lambda rows: ColumnBlock.from_rows(rows, I64_I32),
    lambda rows: Batch.from_tuples(rows, [jnp.int64], [jnp.int32]),
    _parsed], ids=["block_from_rows", "from_tuples", "parser"])
def test_the_sentinel_is_refused_where_host_columns_are_made(build):
    """from_block, like from_columns, is handed columns already held to the
    domain contract: the refusal is where it was."""
    build(_rows(3))
    with pytest.raises(ValueError, match="sentinel"):
        build(_rows(3) + [((7, I32_MAX), 1)])


def test_one_capacity_bucket_is_one_trace_whatever_n():
    """Traces, not seconds: the consolidation inside the program counts its
    path once per trace (kernels.CONSOLIDATE_COUNTS)."""
    from dbsp_tpu.zset import kernels

    dtypes = (jnp.int32, jnp.int32, jnp.int64, jnp.int32)  # no other test's

    def traces_of(n):
        before = sum(kernels.CONSOLIDATE_COUNTS.values())
        rows = [((k % 50, k, -k, 3), 1) for k in range(n)]
        b = Batch.from_block(ColumnBlock.from_rows(rows, dtypes), 3)
        assert int(b.live_count()) == n and b.cap == (512 if n <= 512
                                                      else 1024)
        return sum(kernels.CONSOLIDATE_COUNTS.values()) - before

    assert [traces_of(n) for n in (300, 417, 512, 513, 700, 300)] == \
        [1, 0, 0, 1, 0, 0]


def test_the_handle_says_which_path_built_its_tick():
    handle, h, out = _input()
    op = h._op
    assert op.last_drain == ("device", 0)
    h.extend(ColumnBlock.from_rows(_rows(9), I64_I32))
    h.extend(ColumnBlock.from_rows(_rows(4, 20), I64_I32))
    handle.step()
    assert op.last_drain == ("host_block", 13)
    h.extend(ColumnBlock.from_rows(_rows(9), I64_I32))
    h.push((5, 5), 1)
    handle.step()
    assert op.last_drain == ("mixed", 10)
    h.push_batch(Batch.from_tuples(_rows(3), [jnp.int64], [jnp.int32]))
    handle.step()
    assert op.last_drain == ("device", 8)  # (a pushed batch: its capacity)
    h.extend(_rows(3))
    handle.step()
    assert op.last_drain == ("device", 3)
    handle.step()
    assert op.last_drain == ("device", 0) and out.to_dict() == {}


# -- the served route ---------------------------------------------------------


class _Served:
    def __init__(self):
        self.handle, self.h, self.out = _input()
        catalog = Catalog()
        catalog.register_input("t", self.h, I64_I32)
        catalog.register_output("v", self.out, I64_I32)
        self.ctl = Controller(self.handle, catalog, ControllerConfig(
            min_batch_records=10 ** 9, flush_interval_s=3600.0))
        self.srv = CircuitServer(self.ctl)
        self.rec = self.ctl.spans = self.srv.spans = SpanRecorder(max_steps=64)
        self.srv.start()
        self.base = f"http://127.0.0.1:{self.srv.port}"

    def post(self, body, route="/input_endpoint/t?format=json"):
        req = urllib.request.Request(self.base + route, data=body,
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def buffered(self):
        op = self.h._op
        return len(op._rows) + sum(map(len, op._blocks)) + len(op._batches)

    def spans(self, name, n):
        """The ``n`` closed spans called ``name``, once the ring has them
        (a POST's spans reach it when its ``ingest`` span ends, which is
        after the client has its response)."""
        deadline = time.monotonic() + 30
        while True:
            got = [e for e in self.rec.events()
                   if e["name"] == name and e["ph"] == "E"]
            if len(got) >= n or time.monotonic() > deadline:
                return got
            time.sleep(0.01)


@pytest.fixture
def served():
    s = _Served()
    yield s
    s.srv.stop()
    s.ctl.stop()


BAD_POSTS = {
    "bad_json": b'[1, 2\n',
    "wrong_arity": b'[1, 2, 3]\n',
    "neither_key": b'{"upsert": [1, 2]}\n',
    "sentinel": b'[1, %d]\n' % I32_MAX,
    "past_the_dtype": b'[1, %d]\n' % (I32_MAX + 1),
    "past_int64": b'[%d, 1]\n' % 2 ** 64,
}


@pytest.mark.parametrize("bad", sorted(BAD_POSTS))
def test_one_bad_line_in_5000_answers_400_and_buffers_nothing(served, bad):
    status, ok = served.post(_lines(100))
    assert (status, ok["records"]) == (200, 100)
    before = (served.buffered(), served.ctl.stats()["pushed_records"],
              served.ctl.stats()["parsed_records"])
    body = _lines(3000) + BAD_POSTS[bad] + _lines(1999, 3000)
    assert body.count(b"\n") == 5000
    status, err = served.post(body)
    assert status == 400 and err["error"].startswith("parse error")
    assert (served.buffered(), served.ctl.stats()["pushed_records"],
            served.ctl.stats()["parsed_records"]) == before
    assert before[0] == 100 and before[2] == {"columnar": 100, "fallback": 0}
    served.post(b"", route="/step")
    assert served.out.to_dict() == {(k, k % 97): 1 for k in range(100)}


def test_a_good_post_s_parse_span_says_which_path_took_its_rows(served):
    from dbsp_tpu.obs.instrument import ControllerInstrumentation
    from dbsp_tpu.obs.registry import MetricsRegistry

    n = 2 * CHUNK + 10
    status, ok = served.post(_lines(n))
    assert (status, ok["records"]) == (200, n)
    status, ok = served.post(_lines(5) + b'["9", 9]\n')  # one chunk, irregular
    assert (status, ok["records"]) == (200, 6)
    status, ok = served.post(b"1,2\n3,4,2\n",
                             route="/input_endpoint/t?format=csv")
    assert (status, ok["records"]) == (200, 2)
    assert [(e["args"]["columnar"], e["args"]["fallback"])
            for e in served.spans("ingest.parse", 3)] == [(n, 0), (0, 6),
                                                          (0, 2)]
    assert [e["args"]["records"]
            for e in served.spans("ingest", 3)] == [n, 6, 2]
    assert served.ctl.stats()["parsed_records"] == {"columnar": n,
                                                    "fallback": 8}
    assert len(served.h._op._blocks) == 3 and not served.h._op._rows
    reg = MetricsRegistry()
    ControllerInstrumentation(served.ctl, reg)
    reg.collect()
    assert reg.value("dbsp_tpu_io_parsed_records_total",
                     path="columnar") == n
    assert reg.value("dbsp_tpu_io_parsed_records_total", path="fallback") == 8
    assert reg.value("dbsp_tpu_io_pushed_records_total") == n + 8
    served.post(b"", route="/step")
    want = Counter({(k, k % 97): 1 for k in range(n)})
    want.update({(k, k % 97): 1 for k in range(5)})
    want.update({(9, 9): 1, (1, 2): 1, (3, 4): 2})
    assert served.out.to_dict() == dict(want)


def test_a_post_is_one_block_through_push_rows(served, monkeypatch):
    """``InputCollection.push_rows`` stays the one entry of a POST's rows,
    and what it is handed has a length and slices (the benchmark's planted
    fault keeps half of one POST in ten by replacing it)."""
    from dbsp_tpu.io.catalog import InputCollection

    seen = []

    def push_rows(self, rows):
        seen.append(rows)
        self.handle.extend(rows[: len(rows) // 2])
        return len(rows)

    monkeypatch.setattr(InputCollection, "push_rows", push_rows)
    status, ok = served.post(_lines(10))
    assert (status, ok["records"]) == (200, 10)
    assert len(seen) == 1 and isinstance(seen[0], ColumnBlock)
    served.post(b"", route="/step")
    assert served.out.to_dict() == {(k, k % 97): 1 for k in range(5)}
