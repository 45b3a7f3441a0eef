// Result columns -> JSON response text, with no Python object per value.
//
// Role of the reference's response writers (the Go encoder streams a
// result straight from its column slices; open_src/influx/httpd and
// handler_prom.go): an answer of hundreds of thousands of points is
// formatted from its arrays, never built as a tree of per-point
// containers first.  opengemini_tpu/promql/render.py (a PromQL matrix)
// and opengemini_tpu/query/render.py (an InfluxQL aggregate's rows) own
// the JSON around the values (labels, order, envelope) and are the
// pure-Python references for the bytes written here.
//
// Contract: every float is written exactly as CPython's repr(float)
// writes it — shortest digits that round-trip (std::to_chars; the same
// digits as CPython's dtoa mode 0), exponent form for decimal exponents
// below -4 or above 15 (`1e-05`, `1e+16`, `5e-324`), else fixed
// notation with a trailing `.0` on integers.  Equivalence-tested over
// random bit patterns in tests/test_prom_render.py and
// tests/test_influx_render.py.

#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>

namespace {

inline char* put(char* p, const char* s, size_t n) {
  memcpy(p, s, n);
  return p + n;
}

// repr(float) of a finite v; at most 24 bytes (`-1.2345678901234567e-308`).
inline char* py_repr(char* p, double v) {
  if (v == 0) return std::signbit(v) ? put(p, "-0.0", 4) : put(p, "0.0", 3);
  // scientific: [-]d[.ddd]e(+|-)XX[X] — CPython's own exponent form
  char sci[32];
  char* end = std::to_chars(sci, sci + sizeof sci, v,
                            std::chars_format::scientific).ptr;
  char* e = end - 1;
  while (*e != 'e') --e;
  int exp10 = 0;
  for (const char* q = e + 2; q < end; ++q) exp10 = exp10 * 10 + (*q - '0');
  if (e[1] == '-') exp10 = -exp10;
  int decpt = exp10 + 1;  // value = 0.d1d2... * 10^decpt
  if (decpt > 16 || decpt <= -4) return put(p, sci, end - sci);
  const char* s = sci;
  if (*s == '-') *p++ = *s++;
  char digits[20];
  int nd = 0;
  digits[nd++] = *s++;
  if (*s == '.')
    for (++s; s < e; ++s) digits[nd++] = *s;
  if (decpt <= 0) {
    p = put(p, "0.", 2);
    for (int i = decpt; i < 0; ++i) *p++ = '0';
    return put(p, digits, nd);
  }
  if (decpt < nd) {
    p = put(p, digits, decpt);
    *p++ = '.';
    return put(p, digits + decpt, nd - decpt);
  }
  p = put(p, digits, nd);
  for (int i = nd; i < decpt; ++i) *p++ = '0';
  return put(p, ".0", 2);
}

}  // namespace

extern "C" {

// repr() of n doubles back to back; off[i]..off[i+1] is the i-th.
// Non-finite values read `nan`, `inf`, `-inf` as repr has them.  `out`
// must hold 24 bytes a value.  Returns the bytes written.
int64_t ogt_repr_f64(const double* values, int64_t n, char* out, int64_t* off) {
  char* p = out;
  for (int64_t i = 0; i < n; ++i) {
    off[i] = p - out;
    double v = values[i];
    if (std::isnan(v)) p = put(p, "nan", 3);
    else if (std::isinf(v)) p = v > 0 ? put(p, "inf", 3) : put(p, "-inf", 4);
    else p = py_repr(p, v);
  }
  off[n] = p - out;
  return p - out;
}

// The series of a PromQL matrix answer, joined by ", ": for each row r of
// `rows`, in that order, `head[r]` (its `{"metric": {...}, "values": `),
// then `[[t, "v"], ...]` over the steps valid[r][k] marks, then `}`.
// values/valid are (n_series, n_steps) row-major; ts holds the steps'
// JSON text back to back (ts_off: n_steps + 1 offsets), heads the rows'
// (head_off: n_rows + 1, in `rows` order).  A value is repr(float), or
// NaN, +Inf, -Inf.  Returns the bytes written, or -1 if `cap` could not
// hold the widest possible answer (nothing is written then).
int64_t ogt_render_matrix(const double* values, const uint8_t* valid,
                          int64_t n_steps, const int64_t* rows, int64_t n_rows,
                          const char* ts, const int64_t* ts_off,
                          const char* heads, const int64_t* head_off,
                          char* out, int64_t cap) {
  int64_t ts_max = 0;
  for (int64_t k = 0; k < n_steps; ++k)
    if (ts_off[k + 1] - ts_off[k] > ts_max) ts_max = ts_off[k + 1] - ts_off[k];
  // a point: `[` t `, "` v `"]` and its `, `
  int64_t need = head_off[n_rows] + n_rows * (n_steps * (ts_max + 24 + 8) + 8);
  if (need > cap) return -1;
  char* p = out;
  for (int64_t i = 0; i < n_rows; ++i) {
    if (i) p = put(p, ", ", 2);
    p = put(p, heads + head_off[i], head_off[i + 1] - head_off[i]);
    const double* v = values + rows[i] * n_steps;
    const uint8_t* ok = valid + rows[i] * n_steps;
    *p++ = '[';
    bool first = true;
    for (int64_t k = 0; k < n_steps; ++k) {
      if (!ok[k]) continue;
      if (!first) p = put(p, ", ", 2);
      first = false;
      *p++ = '[';
      p = put(p, ts + ts_off[k], ts_off[k + 1] - ts_off[k]);
      p = put(p, ", \"", 3);
      double x = v[k];
      if (std::isnan(x)) p = put(p, "NaN", 3);
      else if (std::isinf(x)) p = x > 0 ? put(p, "+Inf", 4) : put(p, "-Inf", 4);
      else p = py_repr(p, x);
      p = put(p, "\"]", 2);
    }
    p = put(p, "]}", 2);
  }
  return p - out;
}

// The series of an InfluxQL aggregate answer, joined by ", ": for each
// series g, `head` (its `{"name": ..., "columns": [...], "values": [`),
// then `[t, c0, c1, ...]` for each row w that rowmask[g][w] keeps
// (rowmask null: every row), joined by ", ", then tails[g] (`]}` or
// `], "tags": {...}}`).  Column c's cell is kinds[c][g][w]: 0 `null`,
// 1 repr(fvals[c][g][w]) — `null` if not finite, as the front end
// marshals one —, 2 ivals[c][g][w] in decimal, 3 `true`/`false` from it.
// All arrays are (n_series, n_rows) row-major; ivals[c] / fvals[c] may
// be null where no cell of the column is of that kind.  ts holds the
// rows' time text back to back (ts_off: n_rows + 1 offsets), tails the
// series' (tail_off: n_series + 1).  Returns the bytes written, or -1 if
// `cap` could not hold the widest possible answer (nothing is written).
int64_t ogt_render_rows(int64_t n_cols, const uint8_t* const* kinds,
                        const int64_t* const* ivals,
                        const double* const* fvals, const uint8_t* rowmask,
                        int64_t n_series, int64_t n_rows,
                        const char* ts, const int64_t* ts_off,
                        const char* head, int64_t head_len,
                        const char* tails, const int64_t* tail_off,
                        char* out, int64_t cap) {
  int64_t ts_max = 0;
  for (int64_t w = 0; w < n_rows; ++w)
    if (ts_off[w + 1] - ts_off[w] > ts_max) ts_max = ts_off[w + 1] - ts_off[w];
  // a row: `[` t `]` and its `, `; a cell: `, ` and at most 24 bytes
  // (a float's repr; an int64 takes 20)
  int64_t need = n_series * (head_len + 2) + tail_off[n_series] +
                 n_series * n_rows * (ts_max + 4 + n_cols * 26);
  if (need > cap) return -1;
  char* p = out;
  for (int64_t g = 0; g < n_series; ++g) {
    if (g) p = put(p, ", ", 2);
    p = put(p, head, head_len);
    bool first = true;
    for (int64_t w = 0; w < n_rows; ++w) {
      int64_t at = g * n_rows + w;
      if (rowmask && !rowmask[at]) continue;
      if (!first) p = put(p, ", ", 2);
      first = false;
      *p++ = '[';
      p = put(p, ts + ts_off[w], ts_off[w + 1] - ts_off[w]);
      for (int64_t c = 0; c < n_cols; ++c) {
        p = put(p, ", ", 2);
        switch (kinds[c][at]) {
          case 1: {
            double x = fvals[c][at];
            p = std::isfinite(x) ? py_repr(p, x) : put(p, "null", 4);
            break;
          }
          case 2:
            p = std::to_chars(p, p + 24, ivals[c][at]).ptr;
            break;
          case 3:
            p = ivals[c][at] ? put(p, "true", 4) : put(p, "false", 5);
            break;
          default:
            p = put(p, "null", 4);
        }
      }
      *p++ = ']';
    }
    p = put(p, tails + tail_off[g], tail_off[g + 1] - tail_off[g]);
  }
  return p - out;
}

}  // extern "C"
