"""Blocks and a file as the writer's device profile wrote them
(`OGT_DEVICE_PROFILE=1`, a writer option the tree had until PR 47: int and
float payloads in a raw envelope, tag bit 0x80, no zlib), kept here as the
bytes that writer produced: `storage/encoding.py` promises that such files
stay readable, and no writer in the tree can make these bytes any more.
Decoded values equal the literals bit for bit; the file is read through
`TSFReader`, CRC seals and all."""

import struct

import numpy as np
import pytest

from opengemini_tpu.record import FieldType
from opengemini_tpu.storage import encoding
from opengemini_tpu.storage.tsf import CorruptFile, TSFReader

F64 = np.float64
DELTA, RAW64, CONST = (encoding._T_DELTA, encoding._T_RAW64,
                       encoding._T_CONST)
FLAG = encoding._DEV_FLAG

W8 = [5, 4611686018427387912, 3, 4611686018427400252, 12341,
      4611686018427400248, 12339, 4611686018427412588, 24677,
      4611686018427412584, 24675, 4611686018427424924, 37013]
FLOATS = [0.1, -2.5e+300, 3.141592653589793, float("inf"), -0.0, 5e-324,
          1e+17, 6.02214076e+23]

BLOCKS = {
    # name: (tag, width of a delta or None, hex, values)
    "delta_width_1": (DELTA | FLAG, 1,
        "81060000000000a0d88557341640420f00000000000100ff110380",
        [1600000000000000000, 1600000000001000000, 1600000000002000255,
         1600000000003000272, 1600000000004000275, 1600000000005000403]),
    "delta_width_2": (DELTA | FLAG, 2,
        "8105000000000efad5feffffff005ed0b200000000020000ffff2c01409c",
        [-5000000000, -2000000000, 1000065535, 4000065835, 7000105835]),
    "delta_width_4": (DELTA | FLAG, 4,
        "8105000000070000000000000000000000000000010400000000ffffffff7011"
        "0100005ed0b2",
        [7, 72057594037927943, 144115192370823174, 216172786408821110,
         288230383446749046]),
    "delta_width_8": (DELTA | FLAG, 8,
        "810d0000000500000000000000f9ffffffffffffbf080a000000000000800200"
        "000000000000403000000000008000000000000000000a000000000000800200"
        "000000000000403000000000008000000000000000000a000000000000800200"
        "00000000000040300000000000800000000000000000",
        W8),
    "raw64": (RAW64 | FLAG, None,
        "80080000009a9999999999b93f039300aa4bdd4dfe182d4454fb210940000000"
        "000000f07f0000000000000080010000000000000000a0d8855734764317c557"
        "ca85e1df44",
        FLOATS),
    "const_one_row": (CONST, None,
        "04010000000000a0d8855734160000000000000000",
        [1600000000000000000]),
    "const_five_rows": (CONST, None,
        "04050000000000af715424251400e40b5402000000",
        [1451606400000000000, 1451606410000000000, 1451606420000000000,
         1451606430000000000, 1451606440000000000]),
    "delta_no_rows": (DELTA, None, "0100000000", []),
}

# one per-series chunk of measurement "cpu", sid 7, six rows: the time
# column a flagged delta of width 1, `usage` flagged raw floats with row 2
# invalid, `n` a flagged delta of width 4
TSF_FILE = (
    "4f4754534630320a81060000000000a0d88557341640420f00000000000100ff"
    "1103801503982a80060000009a9999999999b93f039300aa4bdd4dfe182d4454"
    "fb210940000000000000f07f00000000000000800100000000000000cbed15dc"
    "0206000000dcf76b2ffc81060000000700000000000000000000000000000104"
    "00000000ffffffff70110100005ed0b2050000002ce75d7b424d303278016364"
    "60606066482e2865626065282d4e4c4f656464c86362040a33b0830820600393"
    "0b6eb4869b885df67903a639c0620c0cf2409a0988d5a17c4b20cd9800e57001"
    "69569000f3648655de777dff41c43fd4236846862c0887410f4483ac025a6e2e"
    "c4606c666064616e696e6e626162666e6669612ecc606860616061666c696c0c"
    "14b5b4b43032335700bb146a043245aa38482fa97a40ea015ab31b0098000000"
    "00000000a40000007ef74fd54f47545346454e44"
)
TSF_TIMES = BLOCKS["delta_width_1"][3]
TSF_USAGE = FLOATS[:6]
TSF_N = BLOCKS["delta_width_4"][3] + [360287977484676987]


def same_bits(got: np.ndarray, want: list, dtype) -> None:
    assert got.dtype == dtype
    assert got.tobytes() == np.array(want, dtype=dtype).tobytes()


@pytest.mark.parametrize("name", BLOCKS)
def test_a_block_of_the_device_profile_decodes_to_its_values(name):
    tag, width, hexed, values = BLOCKS[name]
    buf = bytes.fromhex(hexed)
    assert buf[0] == tag
    if width is not None:
        assert struct.unpack_from("<B", buf, encoding._DELTA_HEAD - 1) \
            == (width,)
        assert len(buf) == encoding._DELTA_HEAD + width * (len(values) - 1)
    if tag & ~FLAG == RAW64:
        same_bits(encoding.decode_floats(buf), values, F64)
        col = encoding.decode_column(FieldType.FLOAT, buf, b"")
    else:
        same_bits(encoding.decode_ints(buf), values, np.int64)
        col = encoding.decode_column(FieldType.INT, buf, b"")
    assert col.valid.all() and len(col) == len(values)


@pytest.fixture
def tsf_path(tmp_path):
    path = tmp_path / "profile.tsf"
    path.write_bytes(bytes.fromhex(TSF_FILE))
    return path


def test_a_file_of_the_device_profile_reads_through_the_reader(tsf_path):
    r = TSFReader(str(tsf_path))
    try:
        assert r.block_crc and r.measurements() == ["cpu"]
        (chunk,) = r.chunks("cpu")
        assert (chunk.sid, chunk.rows) == (7, 6)
        tags = {name: r._read(loc["v"])[0]
                for name, loc in chunk.cols.items()}
        assert tags == {"usage": RAW64 | FLAG, "n": DELTA | FLAG}
        assert r._read(chunk.time_loc)[0] == DELTA | FLAG
        rec = r.read_chunk("cpu", chunk, cache=False)
        same_bits(rec.times, TSF_TIMES, np.int64)
        same_bits(rec.columns["usage"].values, TSF_USAGE, F64)
        assert rec.columns["usage"].valid.tolist() \
            == [True, True, False, True, True, True]
        same_bits(rec.columns["n"].values, TSF_N, np.int64)
        assert rec.columns["n"].valid.all()
    finally:
        r.close()


def test_a_flipped_payload_byte_of_that_file_is_a_crc_mismatch(tsf_path):
    data = bytearray(tsf_path.read_bytes())
    data[45] ^= 0x01            # inside `usage`'s raw floats
    tsf_path.write_bytes(bytes(data))
    r = TSFReader(str(tsf_path))
    try:
        (chunk,) = r.chunks("cpu")
        with pytest.raises(CorruptFile, match="crc mismatch"):
            r.read_chunk("cpu", chunk, cache=False)
    finally:
        r.close()
