"""core/column.format_rows: lines from columns, byte for byte what the
per-row printers write: every template the OINK commands declare, the
native formatter and the Python one each against the printer's own
lines."""

import io

import numpy as np
import pytest

from gpu_mapreduce_tpu import native
from gpu_mapreduce_tpu.core import column
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.oink import kernels, objects
from gpu_mapreduce_tpu.oink.commands import sssp, tri

N = 1000


def _sssp_line(k, v, fp):
    """The line sssp wrote a vertex before it had a template (its value
    here: the (dist, pred) pair)."""
    fp.write(f"{k} {v[0]:g} {v[1]}\n")


# template, the per-row printer, key words, what the value holds
LINES = {
    "vertex": ("%d", kernels.print_vertex, 1, None),
    "edge": ("%d %d", kernels.print_edge, 2, None),
    "vertex_value": ("%d %d", kernels.print_vertex_value, 1, "u64"),
    "edge_value": ("%d %d %d", kernels.print_edge_value, 2, "u64"),
    "tri": ("%d %d %d", tri.print_tri, 3, None),
    "vertex_rank": ("%d %.8g", kernels.print_vertex_rank, 1, "f64"),
    "sssp": (sssp.RESULT_LINE, _sssp_line, 1, "f64+u64"),
}


def _u64(rng, shape):
    """u64 words over the whole range, its ends among them."""
    x = rng.integers(0, 1 << 64, size=shape, dtype=np.uint64)
    ends = [0, 1, (1 << 64) - 2, 1 << 63]
    x.reshape(-1)[:4] = ends[:x.size]
    return x


def _floats(rng, which: str) -> np.ndarray:
    if which == "specials":
        x = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1e8,
                      99999999.5, 99999999.4, 1e-4, 1e-5, 9.9999e-5, 1e16,
                      123456789.0, 0.1, 1 / 3, 5e-324, 1.7976931348623157e308,
                      -2.5, 100000.0, 999999.5, 1e6, 1e-300])
    elif which == "float32":
        x = rng.standard_normal(N).astype(np.float32)
        x[:3] = [np.float32(0.1), np.float32(1e-5), np.float32(3.4e38)]
    elif which == "ties":
        # exactly representable halves at the ninth significant digit
        # (%.8g rounds them to even) and at the seventh (%g)
        x = np.concatenate([
            rng.integers(10 ** 7, 10 ** 8, N // 2).astype(np.float64) + 0.5,
            rng.integers(10 ** 5, 10 ** 6, N // 2).astype(np.float64) + 0.5])
    else:
        assert which == "random"
        x = (rng.uniform(1.0, 10.0, 10 ** 5)
             * 10.0 ** rng.integers(-300, 301, 10 ** 5)
             * rng.choice([-1.0, 1.0], 10 ** 5))
    return x


CASES = [(line, "u64") for line, spec in LINES.items()
         if spec[3] in (None, "u64")]
CASES += [(line, which) for line in ("vertex_rank", "sssp")
          for which in ("specials", "float32", "ties", "random")]
CASES += [(line, "empty") for line in LINES]


@pytest.mark.parametrize("line,values", CASES,
                         ids=[f"{a}-{b}" for a, b in CASES])
def test_block_lines_equal_the_printers(rng, library, line, values):
    if library == "native" and not native.has_format_rows():
        pytest.skip("the native library has no formatter (g++ before 11)")
    template, printer, kw, vkind = LINES[line]
    floats = (_floats(rng, values) if values not in ("u64", "empty")
              else rng.standard_normal(N))
    n = 0 if values == "empty" else len(floats) if vkind in (
        "f64", "f64+u64") else N
    key = _u64(rng, n if kw == 1 else (n, kw))
    if vkind == "f64":
        arrays, value = [key, floats[:n]], floats[:n].tolist()
    elif vkind == "f64+u64":
        pred = _u64(rng, n)
        arrays = [key, floats[:n], pred]
        value = list(zip(floats[:n].tolist(), pred.tolist()))
    elif vkind == "u64":
        arrays = [key, _u64(rng, n)]
        value = arrays[1].tolist()
    else:
        arrays, value = [key], [0] * n
    fp = io.StringIO()
    keys = key.tolist() if kw == 1 else [tuple(r) for r in key.tolist()]
    for k, v in zip(keys, value):
        printer(k, v, fp)
    cols = column.row_columns(template, arrays)
    assert cols is not None and len(cols) == len(column.row_fields(template))
    got = column.format_rows(template, cols)
    assert got.dtype == np.uint8
    assert got.tobytes() == fp.getvalue().encode()
    # any range of rows is those rows' lines
    if n > 10:
        lines = fp.getvalue().encode().splitlines(keepends=True)
        assert column.format_rows(template, cols, 3, n - 2).tobytes() \
            == b"".join(lines[3:n - 2])


@pytest.mark.parametrize("template,arrays", [
    ("%d %d", [np.arange(4, dtype=np.uint64), np.ones(4)]),
    ("%d %g", [np.arange(4, dtype=np.uint64), np.arange(4)]),
    ("%d", [np.ones(4, bool)]),
    ("%d %d", [np.arange(4, dtype=np.uint64)]),
    ("%d", [np.zeros((4, 2), np.uint64)]),
    ("%g", [np.ones(4, np.longdouble)]),
], ids=["float-under-d", "int-under-g", "bool", "too-few", "too-many",
        "longdouble"])
def test_columns_that_are_not_the_templates_are_refused(template, arrays):
    assert column.row_columns(template, arrays) is None


@pytest.mark.parametrize("template", ["%s", "%d  %d", "%d,%d", "%5d", "%.8f",
                                      "", "%d %.123g"])
def test_a_template_of_other_fields_is_an_error(template):
    with pytest.raises(ValueError):
        column.row_fields(template)


def test_signed_and_narrow_integers_print_as_python_prints_them(rng):
    i64 = rng.integers(-(1 << 63), 1 << 63, N, dtype=np.int64)
    i64[:2] = [-(1 << 63), (1 << 63) - 1]
    i32 = rng.integers(-(1 << 31), 1 << 31, N).astype(np.int32)
    u8 = rng.integers(0, 256, N).astype(np.uint8)
    cols = column.row_columns("%d %d %d", [i64, i32, u8])
    assert [c.dtype for c in cols] == [np.int64, np.int64, np.uint64]
    want = "".join(f"{a} {b} {c}\n" for a, b, c in
                   zip(i64.tolist(), i32.tolist(), u8.tolist()))
    assert column.format_rows("%d %d %d", cols).tobytes() == want.encode()


@pytest.mark.skipif(not native.has_format_rows(),
                    reason="the native library has no formatter")
def test_a_buffer_under_the_native_bound_raises(monkeypatch):
    """mr_format_rows answers -1 for a buffer under its own bound; the
    wrapper raises and hands back no shortened text."""
    class Short:
        def __init__(self, lib):
            self.lib = lib

        def mr_format_rows(self, *args):
            *head, out, cap = args
            return self.lib.mr_format_rows(*head, out, cap) if out is None \
                else self.lib.mr_format_rows(*head, out, cap - 1)

    cols = column.row_columns("%d", [np.arange(5, dtype=np.uint64)])
    assert native.format_rows((-1,), cols, 0, 5).tobytes() == b"0\n1\n2\n3\n4\n"
    monkeypatch.setattr(native, "_lib", Short(native._lib))
    with pytest.raises(RuntimeError, match="mr_format_rows"):
        native.format_rows((-1,), cols, 0, 5)


@pytest.mark.parametrize("key,value,printer,blocks", [
    (np.arange(5, dtype=np.uint64), np.arange(5, dtype=np.uint64),
     kernels.print_vertex_value, True),
    (np.arange(5, dtype=np.uint64), np.linspace(0, 1, 5),
     kernels.print_vertex_value, False),         # a float keeps its repr
    (np.zeros((5, 1), np.uint64), np.zeros(5, np.uint8),
     kernels.print_vertex, False),               # prints as a tuple
    (np.zeros((5, 2), np.uint64), np.zeros(5, np.uint8),
     kernels.print_vertex_value, False),
    (np.zeros((5, 2), np.uint64), np.zeros(5, np.uint8),
     kernels.print_edge, True),                  # the value is not printed
    (np.zeros((5, 2), np.uint64), np.zeros((5, 2), np.uint64),
     kernels.print_edge_value, False),
    (np.arange(5, dtype=np.uint64), np.linspace(0, 1, 5),
     lambda k, v, fp: fp.write(f"{k} {v}\n"), False),   # no template
    (np.arange(5, dtype=np.uint64), np.linspace(0, 1, 5), None, False),
], ids=["ints", "float-under-d", "key-n-by-1", "wide-key", "edge",
        "wide-value", "no-template", "no-printer"])
def test_output_takes_blocks_only_where_the_lines_are_the_same(
        tmp_path, key, value, printer, blocks):
    fr = KVFrame(key, value)
    assert (objects._block_columns(printer, fr) is not None) == blocks
    with open(tmp_path / "block", "w") as fp:
        nblock = objects._write_frame(fp, fr, printer, None)
    assert nblock == (len(fr) if blocks else 0)
    with open(tmp_path / "rows", "w") as fp:
        rows = fr.pairs()
        for k, v in rows:
            if printer is None:
                fp.write(f"{k} {v}\n")
            else:
                printer(k, v, fp)
    assert (tmp_path / "block").read_bytes() == (tmp_path / "rows").read_bytes()
