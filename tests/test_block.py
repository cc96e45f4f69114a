"""Ring-1 substrate tests (reference: presto-spi block/type tests, TestPage.java)."""
import numpy as np
import pytest

from presto_tpu import BIGINT, DOUBLE, VARCHAR, DecimalType, Page, parse_type
from presto_tpu.block import (Block, Dictionary, block_from_strings, empty_page,
                              page_from_arrays, page_from_pylists)
from presto_tpu.types import (BOOLEAN, DATE, INTEGER, common_super_type, DecimalType,
                              VarcharType)


def test_parse_type_roundtrip():
    assert parse_type("bigint") is BIGINT
    assert parse_type("decimal(12,2)") == DecimalType(12, 2)
    assert parse_type("varchar") == VarcharType()
    assert parse_type("varchar(25)") == VarcharType(25)


def test_common_super_type():
    assert common_super_type(BIGINT, INTEGER) is BIGINT
    assert common_super_type(BIGINT, DOUBLE) is DOUBLE
    assert common_super_type(DecimalType(12, 2), BIGINT) == DecimalType(12, 2)
    assert common_super_type(DecimalType(12, 2), DOUBLE) is DOUBLE


def test_dictionary_block():
    b = block_from_strings(["MAIL", "SHIP", "MAIL", None])
    assert b.dictionary.lookup(np.asarray([0, 1])).tolist() == ["MAIL", "SHIP"]
    vals = b.to_pylist()
    assert vals == ["MAIL", "SHIP", "MAIL", None]


def test_page_mask_and_compact():
    page = page_from_arrays([BIGINT, DOUBLE],
                            [np.arange(10), np.arange(10) * 0.5],
                            count=10, capacity=16)
    assert page.capacity == 16
    assert page.size() == 10
    # select even rows via mask, then compact
    mask = np.asarray(page.mask) & (np.arange(16) % 2 == 0)
    filtered = page.with_mask(mask).compact()
    assert filtered.size() == 5
    rows = filtered.to_pylists()
    assert [r[0] for r in rows] == [0, 2, 4, 6, 8]
    assert [r[1] for r in rows] == [0.0, 1.0, 2.0, 3.0, 4.0]


def test_page_from_pylists_decimal_and_null():
    page = page_from_pylists([BIGINT, DecimalType(10, 2)],
                             [[1, "3.50"], [2, None], [None, "1.25"]])
    rows = page.to_pylists()
    from decimal import Decimal
    assert rows[0] == [1, Decimal("3.50")]
    assert rows[1][1] is None
    assert rows[2][0] is None


def test_empty_page():
    p = empty_page([BIGINT, VARCHAR], capacity=8)
    assert p.size() == 0
    assert p.to_pylists() == []


def test_compact_full_capacity():
    # all rows live: compact must be identity
    page = page_from_arrays([INTEGER], [np.arange(8)], count=8, capacity=8)
    c = page.compact()
    assert c.size() == 8
    assert [r[0] for r in c.to_pylists()] == list(range(8))


def _compact_case(kind, nulls, live, cap):
    """A page of one `kind` column whose every slot holds something a leak
    would show (no zero, no False at row 0), and the mask `live` names."""
    rng = np.random.default_rng(cap * 31 + len(kind) + len(live))
    rows = np.arange(cap)
    dictionary = None
    if kind == "dictionary":
        dictionary = Dictionary([f"v{i}" for i in range(8)])
        type_, data = VARCHAR, (rows % 7 + 1).astype(np.int32)
    elif kind == "bool":
        type_, data = BOOLEAN, rows % 3 != 1
    else:
        type_ = {"int32": INTEGER, "int64": BIGINT, "float64": DOUBLE}[kind]
        data = ((rows + 1) * 7 * (2 ** 33 if kind == "int64" else 1) *
                (0.37 if kind == "float64" else 1)).astype(type_.np_dtype)
    null_mask = None
    if nulls:
        null_mask = rng.random(cap) < 0.3
        null_mask[0] = True
    mask = {"all": np.ones(cap, dtype=bool), "none": np.zeros(cap, dtype=bool),
            "one": rows == cap // 2, "random": rng.random(cap) < 0.4}[live]
    return Page((Block(type_, data, null_mask, dictionary),), mask)


@pytest.mark.parametrize("cap", [1, 16, 4096])
@pytest.mark.parametrize("live", ["all", "none", "one", "random"])
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("kind", ["int32", "int64", "float64", "bool",
                                  "dictionary"])
def test_compact_equals_numpy_reference(kind, nulls, live, cap):
    """Page.compact() against plain numpy: live rows in order, then zeros /
    False, mask a prefix — bit for bit, with type and dictionary kept."""
    page = _compact_case(kind, nulls, live, cap)
    block = page.blocks[0]
    keep = np.flatnonzero(page.mask)

    def packed(a):
        out = np.zeros_like(np.asarray(a))
        out[:len(keep)] = np.asarray(a)[keep]
        return out

    got = page.compact()
    out = got.blocks[0]
    assert out.type is block.type and out.dictionary is block.dictionary
    assert np.asarray(out.data).dtype == np.asarray(block.data).dtype
    # bytes, not values: -0.0 or a NaN payload would not slip through
    assert np.asarray(out.data).tobytes() == packed(block.data).tobytes()
    if nulls:
        assert np.array_equal(np.asarray(out.nulls), packed(block.nulls))
    else:
        assert out.nulls is None
    assert np.array_equal(np.asarray(got.mask), np.arange(cap) < len(keep))
    assert got.size() == len(keep)
