/* RLP codec as a CPython extension — the hot host loop of trie commits
 * — and, further down, the trie's look-up walk over the nodes it decodes.
 *
 * Semantics are bit-identical to khipu_tpu/base/rlp.py (the pure-Python
 * reference implementation, kept as the no-toolchain fallback and as
 * the differential oracle in tests): Yellow Paper appendix B encoding,
 * canonical-form enforcement on decode, MAX_DEPTH nesting cap.
 * Role parity: khipu-base/src/main/scala/khipu/rlp/RLP.scala:35.
 *
 * Errors raise the exception class installed via _set_error (the
 * package passes base.rlp.RLPError so callers see one exception type
 * regardless of backend).
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define MAX_DEPTH 64

static PyObject *rlp_error = NULL; /* set via _set_error */
static PyObject *enc_hook = NULL;  /* test-only: runs between passes */

static void set_err(const char *msg) {
  PyErr_SetString(rlp_error ? rlp_error : PyExc_ValueError, msg);
}

/* ------------------------------------------------------------ encode */

static int enc_size(PyObject *o, Py_ssize_t *out, int depth) {
  const char *buf;
  Py_ssize_t n;
  if (PyBytes_CheckExact(o)) {
    buf = PyBytes_AS_STRING(o);
    n = PyBytes_GET_SIZE(o);
  } else if (PyByteArray_CheckExact(o)) {
    buf = PyByteArray_AS_STRING(o);
    n = PyByteArray_GET_SIZE(o);
  } else if (PyList_CheckExact(o) || PyTuple_CheckExact(o)) {
    if (depth >= MAX_DEPTH) {
      set_err("RLP nesting exceeds MAX_DEPTH");
      return -1;
    }
    int is_list = PyList_CheckExact(o);
    Py_ssize_t k = is_list ? PyList_GET_SIZE(o) : PyTuple_GET_SIZE(o);
    Py_ssize_t total = 0;
    for (Py_ssize_t i = 0; i < k; ++i) {
      PyObject *c = is_list ? PyList_GET_ITEM(o, i) : PyTuple_GET_ITEM(o, i);
      Py_ssize_t s;
      if (enc_size(c, &s, depth + 1) < 0) return -1;
      total += s;
    }
    if (total < 56) {
      *out = 1 + total;
    } else {
      Py_ssize_t l = total, lb = 0;
      while (l) { lb++; l >>= 8; }
      *out = 1 + lb + total;
    }
    return 0;
  } else {
    set_err("cannot RLP-encode object (want bytes or list)");
    return -1;
  }
  if (n == 1 && (unsigned char)buf[0] < 0x80) {
    *out = 1;
  } else if (n < 56) {
    *out = 1 + n;
  } else {
    Py_ssize_t l = n, lb = 0;
    while (l) { lb++; l >>= 8; }
    *out = 1 + lb + n;
  }
  return 0;
}

/* The write pass is CLAMPED to the buffer sized by enc_size: a
 * bytearray resized between the two passes (e.g. by a GC finalizer
 * running on an allocation inside py_encode) must never let memcpy
 * run past the output bytes object. Every write site bounds-checks
 * against `end`; py_encode additionally requires the exact sized
 * length to be produced, so a shrink is rejected too. */

static char *write_len(char *p, const char *end, Py_ssize_t n,
                       unsigned char offset) {
  if (n < 56) {
    if (end - p < 1) { set_err("RLP input resized during encode"); return NULL; }
    *p++ = (char)(offset + n);
    return p;
  }
  unsigned char tmp[sizeof(Py_ssize_t)];
  int lb = 0;
  Py_ssize_t l = n;
  while (l) { tmp[lb++] = (unsigned char)(l & 0xFF); l >>= 8; }
  if (end - p < 1 + lb) { set_err("RLP input resized during encode"); return NULL; }
  *p++ = (char)(offset + 55 + lb);
  for (int i = lb - 1; i >= 0; --i) *p++ = (char)tmp[i];
  return p;
}

static char *enc_write(PyObject *o, char *p, const char *end, int depth) {
  const char *buf;
  Py_ssize_t n;
  if (PyBytes_CheckExact(o)) {
    buf = PyBytes_AS_STRING(o);
    n = PyBytes_GET_SIZE(o);
  } else if (PyByteArray_CheckExact(o)) {
    buf = PyByteArray_AS_STRING(o);
    n = PyByteArray_GET_SIZE(o);
  } else {
    int is_list = PyList_CheckExact(o);
    Py_ssize_t k = is_list ? PyList_GET_SIZE(o) : PyTuple_GET_SIZE(o);
    Py_ssize_t total = 0;
    for (Py_ssize_t i = 0; i < k; ++i) {
      PyObject *c = is_list ? PyList_GET_ITEM(o, i) : PyTuple_GET_ITEM(o, i);
      Py_ssize_t s;
      if (enc_size(c, &s, depth + 1) < 0) return NULL;
      total += s;
    }
    p = write_len(p, end, total, 0xC0);
    if (p == NULL) return NULL;
    for (Py_ssize_t i = 0; i < k; ++i) {
      PyObject *c = is_list ? PyList_GET_ITEM(o, i) : PyTuple_GET_ITEM(o, i);
      p = enc_write(c, p, end, depth + 1);
      if (p == NULL) return NULL;
    }
    return p;
  }
  if (n == 1 && (unsigned char)buf[0] < 0x80) {
    if (end - p < 1) { set_err("RLP input resized during encode"); return NULL; }
    *p++ = buf[0];
    return p;
  }
  p = write_len(p, end, n, 0x80);
  if (p == NULL) return NULL;
  if (n > end - p) { set_err("RLP input resized during encode"); return NULL; }
  memcpy(p, buf, n);
  return p + n;
}

static PyObject *py_encode(PyObject *self, PyObject *o) {
  Py_ssize_t size;
  if (enc_size(o, &size, 0) < 0) return NULL;
  if (enc_hook != NULL) { /* test-only seam for the resize race */
    PyObject *r = PyObject_CallObject(enc_hook, NULL);
    if (!r) return NULL;
    Py_DECREF(r);
  }
  PyObject *out = PyBytes_FromStringAndSize(NULL, size);
  if (!out) return NULL;
  char *buf = PyBytes_AS_STRING(out);
  char *end = enc_write(o, buf, buf + size, 0);
  if (end == NULL) {
    Py_DECREF(out);
    return NULL;
  }
  if (end != buf + size) { /* shrank between passes */
    Py_DECREF(out);
    set_err("RLP input resized during encode");
    return NULL;
  }
  return out;
}

/* ------------------------------------------------------------ decode */

static PyObject *dec_at(const unsigned char *d, Py_ssize_t len,
                        Py_ssize_t pos, Py_ssize_t *end_out, int depth);

static PyObject *dec_list(const unsigned char *d, Py_ssize_t len,
                          Py_ssize_t start, Py_ssize_t end, int depth) {
  if (depth >= MAX_DEPTH) {
    set_err("RLP nesting exceeds MAX_DEPTH");
    return NULL;
  }
  PyObject *items = PyList_New(0);
  if (!items) return NULL;
  Py_ssize_t pos = start;
  while (pos < end) {
    Py_ssize_t next;
    PyObject *item = dec_at(d, len, pos, &next, depth + 1);
    if (!item) { Py_DECREF(items); return NULL; }
    if (next > end) {
      Py_DECREF(item);
      Py_DECREF(items);
      set_err("list element overruns list payload");
      return NULL;
    }
    if (PyList_Append(items, item) < 0) {
      Py_DECREF(item);
      Py_DECREF(items);
      return NULL;
    }
    Py_DECREF(item);
    pos = next;
  }
  return items;
}

static PyObject *dec_at(const unsigned char *d, Py_ssize_t len,
                        Py_ssize_t pos, Py_ssize_t *end_out, int depth) {
  if (pos >= len) {
    set_err("truncated RLP input");
    return NULL;
  }
  unsigned char b0 = d[pos];
  if (b0 < 0x80) {
    *end_out = pos + 1;
    return PyBytes_FromStringAndSize((const char *)d + pos, 1);
  }
  if (b0 <= 0xB7) { /* short string */
    Py_ssize_t n = b0 - 0x80;
    Py_ssize_t end = pos + 1 + n;
    if (end > len) { set_err("truncated string"); return NULL; }
    if (n == 1 && d[pos + 1] < 0x80) {
      set_err("non-canonical single byte");
      return NULL;
    }
    *end_out = end;
    return PyBytes_FromStringAndSize((const char *)d + pos + 1, n);
  }
  if (b0 <= 0xBF) { /* long string */
    Py_ssize_t ll = b0 - 0xB7;
    if (pos + 1 + ll > len) { set_err("truncated length"); return NULL; }
    Py_ssize_t n = 0;
    for (Py_ssize_t i = 0; i < ll; ++i) {
      if (n > (PY_SSIZE_T_MAX >> 8)) { set_err("length overflow"); return NULL; }
      n = (n << 8) | d[pos + 1 + i];
    }
    if (n < 56 || (ll > 1 && d[pos + 1] == 0)) {
      set_err("non-canonical length");
      return NULL;
    }
    Py_ssize_t start = pos + 1 + ll;
    /* n can be near PY_SSIZE_T_MAX: compare by subtraction, never
       compute start + n (signed overflow is UB) */
    if (n > len - start) { set_err("truncated string"); return NULL; }
    Py_ssize_t end = start + n;
    *end_out = end;
    return PyBytes_FromStringAndSize((const char *)d + start, n);
  }
  if (b0 <= 0xF7) { /* short list */
    Py_ssize_t n = b0 - 0xC0;
    Py_ssize_t end = pos + 1 + n;
    if (end > len) { set_err("truncated list"); return NULL; }
    PyObject *items = dec_list(d, len, pos + 1, end, depth);
    if (!items) return NULL;
    *end_out = end;
    return items;
  }
  /* long list */
  Py_ssize_t ll = b0 - 0xF7;
  if (pos + 1 + ll > len) { set_err("truncated length"); return NULL; }
  Py_ssize_t n = 0;
  for (Py_ssize_t i = 0; i < ll; ++i) {
    if (n > (PY_SSIZE_T_MAX >> 8)) { set_err("length overflow"); return NULL; }
    n = (n << 8) | d[pos + 1 + i];
  }
  if (n < 56 || (ll > 1 && d[pos + 1] == 0)) {
    set_err("non-canonical length");
    return NULL;
  }
  Py_ssize_t start = pos + 1 + ll;
  if (n > len - start) { set_err("truncated list"); return NULL; }
  Py_ssize_t end = start + n;
  PyObject *items = dec_list(d, len, start, end, depth);
  if (!items) return NULL;
  *end_out = end;
  return items;
}

/* One item that fills the whole buffer, as `decode` promises. */
static PyObject *dec_exact(const unsigned char *d, Py_ssize_t len) {
  Py_ssize_t end;
  PyObject *item = dec_at(d, len, 0, &end, 0);
  if (item && end != len) {
    Py_DECREF(item);
    item = NULL;
    set_err("trailing bytes after RLP item");
  }
  return item;
}

static PyObject *py_decode(PyObject *self, PyObject *arg) {
  Py_buffer view;
  if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return NULL;
  PyObject *item = dec_exact((const unsigned char *)view.buf, view.len);
  PyBuffer_Release(&view);
  return item;
}

static PyObject *py_set_error(PyObject *self, PyObject *cls) {
  Py_XINCREF(cls);
  Py_XDECREF(rlp_error);
  rlp_error = cls;
  Py_RETURN_NONE;
}

static PyObject *py_set_encode_hook(PyObject *self, PyObject *cb) {
  /* Test-only: install a callable invoked between the size and write
   * passes of encode (None clears). Lets tests exercise the
   * resized-input guard deterministically. */
  if (cb == Py_None) cb = NULL;
  Py_XINCREF(cb);
  Py_XDECREF(enc_hook);
  enc_hook = cb;
  Py_RETURN_NONE;
}

/* -------------------------------------------------- snappy compress
 *
 * Greedy Snappy block-format compressor (the devp2p p2p/v5 frame
 * codec): a 16-bit hash table finds 4-byte matches within a 64 KiB
 * window; matches emit copy-with-2-byte-offset ops (<= 64 bytes per
 * op), gaps emit literals. Output is accepted by any spec decoder —
 * the Python decompress in network/snappy_codec.py round-trips it in
 * tests. Role parity: the reference links snappy-java (SURVEY §2.10).
 */

#define SNAPPY_HASH_BITS 14
#define SNAPPY_HASH_SIZE (1 << SNAPPY_HASH_BITS)

static inline uint32_t snappy_hash(const unsigned char *p) {
  uint32_t v = (uint32_t)p[0] | ((uint32_t)p[1] << 8) |
               ((uint32_t)p[2] << 16) | ((uint32_t)p[3] << 24);
  return (v * 0x1E35A7BDu) >> (32 - SNAPPY_HASH_BITS);
}

static unsigned char *emit_literal(unsigned char *op,
                                   const unsigned char *base,
                                   Py_ssize_t len) {
  while (len > 0) {
    Py_ssize_t n = len;
    if (n > 65536) n = 65536; /* keep extended length <= 2 bytes */
    if (n <= 60) {
      *op++ = (unsigned char)((n - 1) << 2);
    } else if (n <= 256) {
      *op++ = 60 << 2;
      *op++ = (unsigned char)(n - 1);
    } else {
      *op++ = 61 << 2;
      *op++ = (unsigned char)((n - 1) & 0xFF);
      *op++ = (unsigned char)(((n - 1) >> 8) & 0xFF);
    }
    memcpy(op, base, n);
    op += n;
    base += n;
    len -= n;
  }
  return op;
}

static unsigned char *emit_copy(unsigned char *op, Py_ssize_t offset,
                                Py_ssize_t len) {
  /* copy2: 6-bit (len-1), 16-bit LE offset; split long matches */
  while (len > 0) {
    Py_ssize_t n = len;
    if (n > 64) n = 64;
    if (n < 4) break; /* never emit a <4-byte copy (tail folds into
                         the next literal) */
    *op++ = (unsigned char)(((n - 1) << 2) | 2);
    *op++ = (unsigned char)(offset & 0xFF);
    *op++ = (unsigned char)((offset >> 8) & 0xFF);
    len -= n;
  }
  return op;
}

static PyObject *py_snappy_compress(PyObject *self, PyObject *arg) {
  Py_buffer view;
  if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return NULL;
  const unsigned char *src = (const unsigned char *)view.buf;
  Py_ssize_t n = view.len;
  /* worst-case output bound (snappy's MaxCompressedLength formula):
     greedy emission can EXPAND — e.g. alternating short literal runs
     (2-3 header bytes each) with 4-byte copies that save only 1 — so
     the slack must scale with n/6, not per-64KiB */
  Py_ssize_t cap = 32 + n + n / 6;
  unsigned char *buf = (unsigned char *)PyMem_Malloc(cap < 16 ? 16 : cap);
  if (!buf) {
    PyBuffer_Release(&view);
    return PyErr_NoMemory();
  }
  unsigned char *op = buf;
  Py_ssize_t v = n;
  do { /* varint uncompressed length */
    unsigned char b = (unsigned char)(v & 0x7F);
    v >>= 7;
    *op++ = v ? (b | 0x80) : b;
  } while (v);

  uint16_t table[SNAPPY_HASH_SIZE];
  memset(table, 0, sizeof(table));
  /* table stores pos+1 within the current 64 KiB-aligned region, so a
     zero entry means empty; offsets are validated against the window */
  Py_ssize_t lit_start = 0;
  Py_ssize_t i = 0;
  while (i + 4 <= n) {
    uint32_t h = snappy_hash(src + i);
    Py_ssize_t cand = (Py_ssize_t)table[h] - 1 +
                      (i & ~(Py_ssize_t)0xFFFF);
    if (cand >= i) cand -= 65536;
    table[h] = (uint16_t)((i & 0xFFFF) + 1);
    if (cand >= 0 && cand < i && i - cand <= 65535 &&
        memcmp(src + cand, src + i, 4) == 0) {
      /* extend the match */
      Py_ssize_t len = 4;
      while (i + len < n && src[cand + len] == src[i + len] &&
             len < 65536)
        ++len;
      op = emit_literal(op, src + lit_start, i - lit_start);
      /* emit_copy splits at 64 and refuses a <4-byte tail — compute
         the coverable length so the tail folds into the next literal */
      Py_ssize_t covered = len - (len % 64);
      Py_ssize_t tail = len % 64;
      if (tail >= 4) covered += tail;
      op = emit_copy(op, i - cand, covered);
      i += covered;
      lit_start = i;
      continue;
    }
    ++i;
  }
  op = emit_literal(op, src + lit_start, n - lit_start);
  PyObject *out = PyBytes_FromStringAndSize((const char *)buf, op - buf);
  PyMem_Free(buf);
  PyBuffer_Release(&view);
  return out;
}

/* ------------------------------------------------------ trie look-up
 *
 * MerklePatriciaTrie.get as one call: the walk of trie/mpt.py's `_get`
 * with the reference resolution of its `_resolve`, in the same order
 * (decoded-node cache, then the session's staged and live log entries
 * decoded here and NOT cached, then the source). The source is not
 * read from here: a reference none of the three maps answers goes
 * back to Python's `_resolve`, which reads it, fills the cache under
 * its bound and raises MPTNodeMissingException, all as it does for the
 * Python walk. A node of a shape the walk does not know (anything but
 * a 2- or 17-item list with a bytes path) is handed to Python's `_get`
 * with the nibbles that are left, so a malformed node fails exactly as
 * it did.
 *
 * The GIL is held throughout, and the maps are the ones the Python
 * walk reads, so `_resolve`'s thread-safety argument is unchanged. A
 * call-back, though, can let go of the GIL, and another thread may
 * then clear() the decoded cache or prune the staged map: the walk
 * owns a reference to the node it stands on and to the child ref it
 * asks for across every call-back, and to the three maps for the
 * whole call.
 *
 * Counters (the process's, read by trie_counters): look-ups, the
 * walk's own nanoseconds (two clock reads a look-up, the call-backs'
 * time taken out with two more a call-back) and call-backs.
 */

#include <time.h>

void khipu_keccak(int rate, const uint8_t *in, uint64_t in_len,
                  uint8_t *out, int out_len); /* csrc/keccak.cc */

static uint64_t trie_reads = 0, trie_walk_ns = 0, trie_callbacks = 0;
static PyObject *s_root_ref, *s_dcache, *s_staged, *s_logs, *s_resolve,
    *s_get;

static inline uint64_t now_ns(void) {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

static inline unsigned nibble_at(const unsigned char *d, Py_ssize_t i) {
  return (i & 1) ? (d[i >> 1] & 0x0F) : (d[i >> 1] >> 4);
}

typedef struct {
  PyObject *trie, *dcache, *staged, *logs; /* owned for the call */
  uint64_t callback_ns;
} walk_t;

/* `_resolve`: a NEW reference to the node behind `ref`, NULL on error. */
static PyObject *walk_resolve(walk_t *w, PyObject *ref) {
  if (PyList_Check(ref) || (PyBytes_CheckExact(ref) &&
                            PyBytes_GET_SIZE(ref) == 0)) {
    Py_INCREF(ref); /* an inline node, or BLANK */
    return ref;
  }
  if (PyBytes_CheckExact(ref) && PyDict_CheckExact(w->dcache) &&
      PyDict_CheckExact(w->staged) && PyDict_CheckExact(w->logs)) {
    PyObject *node = PyDict_GetItemWithError(w->dcache, ref);
    if (node != NULL && node != Py_None) {
      Py_INCREF(node);
      return node;
    }
    if (node == NULL && PyErr_Occurred()) return NULL;
    PyObject *enc = PyDict_GetItemWithError(w->staged, ref);
    if (enc == NULL && PyErr_Occurred()) return NULL;
    if (enc == NULL || enc == Py_None) {
      enc = NULL;
      PyObject *log = PyDict_GetItemWithError(w->logs, ref);
      if (log == NULL && PyErr_Occurred()) return NULL;
      if (log != NULL && PyList_CheckExact(log) &&
          PyList_GET_SIZE(log) == 2 &&
          PyLong_CheckExact(PyList_GET_ITEM(log, 0))) {
        int overflow;
        long count = PyLong_AsLongAndOverflow(PyList_GET_ITEM(log, 0),
                                              &overflow);
        if ((count > 0 || overflow > 0) &&
            PyList_GET_ITEM(log, 1) != Py_None)
          enc = PyList_GET_ITEM(log, 1);
      } else if (log != NULL) {
        goto python; /* not a [count, encoded] record: Python's say */
      }
    }
    if (enc != NULL) {
      if (!PyBytes_CheckExact(enc)) goto python;
      Py_INCREF(enc); /* decoding allocates: a finalizer may run */
      node = dec_exact((const unsigned char *)PyBytes_AS_STRING(enc),
                       PyBytes_GET_SIZE(enc));
      Py_DECREF(enc);
      return node;
    }
  }
python:;
  uint64_t t0 = now_ns();
  PyObject *node = PyObject_CallMethodOneArg(w->trie, s_resolve, ref);
  w->callback_ns += now_ns() - t0;
  trie_callbacks++;
  return node;
}

/* The Python walk from `node` on, with the nibbles from `pos`. */
static PyObject *walk_in_python(walk_t *w, PyObject *node,
                                const unsigned char *key, Py_ssize_t pos,
                                Py_ssize_t n) {
  PyObject *rest = PyBytes_FromStringAndSize(NULL, n - pos);
  if (!rest) return NULL;
  char *p = PyBytes_AS_STRING(rest);
  for (Py_ssize_t i = pos; i < n; ++i) *p++ = (char)nibble_at(key, i);
  PyObject *out = PyObject_CallMethodObjArgs(w->trie, s_get, node, rest,
                                             NULL);
  Py_DECREF(rest);
  return out;
}

/* The walk proper: `key` is n nibbles, two a byte. */
static PyObject *walk_get(walk_t *w, PyObject *root_ref,
                          const unsigned char *key, Py_ssize_t n) {
  PyObject *node = walk_resolve(w, root_ref); /* owned */
  Py_ssize_t pos = 0;
  while (node != NULL) {
    PyObject *out = NULL, *child = NULL;
    if (PyBytes_CheckExact(node) && PyBytes_GET_SIZE(node) == 0) {
      out = Py_NewRef(Py_None); /* BLANK: absent */
    } else if (PyList_CheckExact(node) && PyList_GET_SIZE(node) == 17) {
      if (pos == n) { /* the branch's own value, `node[16] or None` */
        PyObject *v = PyList_GET_ITEM(node, 16);
        int truth = PyObject_IsTrue(v);
        if (truth >= 0) out = Py_NewRef(truth ? v : Py_None);
      } else {
        child = PyList_GET_ITEM(node, nibble_at(key, pos));
        pos += 1;
      }
    } else if (PyList_CheckExact(node) && PyList_GET_SIZE(node) == 2 &&
               PyBytes_CheckExact(PyList_GET_ITEM(node, 0)) &&
               PyBytes_GET_SIZE(PyList_GET_ITEM(node, 0)) > 0) {
      PyObject *hp = PyList_GET_ITEM(node, 0);
      const unsigned char *d = (const unsigned char *)PyBytes_AS_STRING(hp);
      unsigned flag = d[0] >> 4;
      Py_ssize_t skip = (flag & 1) ? 1 : 2; /* nibbles before the path */
      Py_ssize_t plen = 2 * PyBytes_GET_SIZE(hp) - skip;
      Py_ssize_t left = n - pos;
      int same = (flag & 2) ? plen == left : plen <= left;
      for (Py_ssize_t j = 0; same && j < plen; ++j)
        same = nibble_at(d, skip + j) == nibble_at(key, pos + j);
      if (!same) {
        out = Py_NewRef(Py_None); /* diverges in a leaf or extension */
      } else if (flag & 2) {
        out = Py_NewRef(PyList_GET_ITEM(node, 1)); /* the leaf's value */
      } else {
        child = PyList_GET_ITEM(node, 1);
        pos += plen;
      }
    } else {
      out = walk_in_python(w, node, key, pos, n);
    }
    if (child == NULL) { /* answered (or failed: `out` is NULL) */
      Py_DECREF(node);
      return out;
    }
    Py_INCREF(child); /* borrowed from `node`, which stays owned too */
    PyObject *next = walk_resolve(w, child);
    Py_DECREF(child);
    Py_DECREF(node);
    node = next;
  }
  return NULL;
}

/* trie_get(trie, key, hashed): trie.get(key), or with `hashed` true
 * trie.get(keccak256(key)). */
static PyObject *py_trie_get(PyObject *self, PyObject *const *args,
                             Py_ssize_t nargs) {
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError, "trie_get(trie, key, hashed)");
    return NULL;
  }
  int hashed = PyObject_IsTrue(args[2]);
  if (hashed < 0) return NULL;
  Py_buffer view;
  if (PyObject_GetBuffer(args[1], &view, PyBUF_SIMPLE) < 0) return NULL;
  uint64_t t0 = now_ns();
  trie_reads++;
  unsigned char digest[32];
  const unsigned char *key = (const unsigned char *)view.buf;
  Py_ssize_t n = 2 * view.len;
  if (hashed) {
    khipu_keccak(136, key, (uint64_t)view.len, digest, 32);
    key = digest;
    n = 64;
  }
  walk_t w = {args[0], NULL, NULL, NULL, 0};
  PyObject *out = NULL;
  PyObject *root_ref = PyObject_GetAttr(w.trie, s_root_ref);
  w.dcache = PyObject_GetAttr(w.trie, s_dcache);
  w.staged = PyObject_GetAttr(w.trie, s_staged);
  w.logs = PyObject_GetAttr(w.trie, s_logs);
  if (root_ref && w.dcache && w.staged && w.logs)
    out = walk_get(&w, root_ref, key, n);
  Py_XDECREF(root_ref);
  Py_XDECREF(w.dcache);
  Py_XDECREF(w.staged);
  Py_XDECREF(w.logs);
  PyBuffer_Release(&view);
  trie_walk_ns += now_ns() - t0 - w.callback_ns;
  return out;
}

static PyObject *py_trie_counters(PyObject *self, PyObject *noargs) {
  return Py_BuildValue("(KKK)", (unsigned long long)trie_reads,
                       (unsigned long long)trie_walk_ns,
                       (unsigned long long)trie_callbacks);
}

static PyMethodDef methods[] = {
    {"encode", py_encode, METH_O, "RLP-encode bytes / nested lists."},
    {"decode", py_decode, METH_O, "RLP-decode one item (strict)."},
    {"_set_error", py_set_error, METH_O, "Install the error class."},
    {"_set_encode_hook", py_set_encode_hook, METH_O,
     "Test-only: callable run between encode's size and write passes."},
    {"snappy_compress", py_snappy_compress, METH_O,
     "Greedy Snappy block-format compression."},
    {"trie_get", (PyCFunction)(void (*)(void))py_trie_get, METH_FASTCALL,
     "trie_get(trie, key, hashed): MerklePatriciaTrie.get in one call."},
    {"trie_counters", py_trie_counters, METH_NOARGS,
     "(look-ups, the walk's own ns, call-backs) since the load."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "khipu_rlp_ext", NULL, -1, methods,
};

PyMODINIT_FUNC PyInit_khipu_rlp_ext(void) {
  s_root_ref = PyUnicode_InternFromString("_root_ref");
  s_dcache = PyUnicode_InternFromString("_dcache");
  s_staged = PyUnicode_InternFromString("_staged");
  s_logs = PyUnicode_InternFromString("_logs");
  s_resolve = PyUnicode_InternFromString("_resolve");
  s_get = PyUnicode_InternFromString("_get");
  if (!s_root_ref || !s_dcache || !s_staged || !s_logs || !s_resolve ||
      !s_get)
    return NULL;
  return PyModule_Create(&moduledef);
}
