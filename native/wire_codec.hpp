// Native executor of cometbft_tpu/wire/proto.py's message descriptors.
//
// wire/proto.py stays the one definition of the wire format: a Msg
// descriptor (name + fields F(num, name, kind, msg, repeated, always,
// tag)) is compiled ONCE into a C table, cached on the Msg object,
// and encode/decode walk that table in place of the Python walk.
// Recursion follows the descriptor, never the data, and a descriptor
// nests at most kMaxDepth deep (compile refuses a deeper one), so the
// C stack is bounded whatever a peer sends.
//
// Semantics are the Python walk's, to the byte (its docstring lists
// them).  What this file was not written for it DECLINES: the call
// returns None with no exception set and the Python walk answers, so
// behaviour on odd inputs is the walk's:
//   encode: a message that is not a dict, a value whose type is not
//           the field's (int kinds take int/bool, bytes takes
//           bytes/bytearray/memoryview, string takes str, repeated
//           takes list/tuple), an integer outside the kind's range;
//   decode: data that is not bytes/bytearray, a 10-byte varint whose
//           last byte carries more than bit 63 (the walk returns an
//           integer above 2^64 there);
//   both:   a descriptor that does not compile.
//
// decode() takes bytes from peers: every length is checked against
// the end of the buffer before a read, a varint is at most 10 bytes,
// no allocation is sized by a length the buffer does not hold, and
// the input is pinned by a Py_buffer for the whole call.
#pragma once

#include <Python.h>

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

namespace wire {
namespace {

constexpr int kMaxDepth = 32;
constexpr const char* kCapsuleName = "cometbft_tpu.wire_table";

enum Kind : uint8_t {
    kInt32, kInt64, kEnum, kUint32, kUint64, kBool,
    kSfixed64, kFixed64, kSfixed32, kFixed32, kBytes, kString, kMsg,
};

struct Table;

struct Field {
    uint64_t num;
    PyObject* name;         // interned dict key, owned
    Kind kind;
    bool repeated;
    bool always;
    uint8_t tag_len;
    uint8_t tag[10];
    const Table* sub;       // kept alive by sub_capsule
    PyObject* sub_capsule;  // owned, kMsg only
};

struct Table {
    PyObject* name = nullptr;        // desc.name, for error messages
    std::vector<Field> fields;       // ascending field number
    std::vector<int16_t> by_num;     // field number -> index, or -1
    std::vector<uint16_t> always;    // indices decode defaults to {}
    int height = 1;                  // levels of messages below and with it

    ~Table() {
        Py_XDECREF(name);
        for (Field& f : fields) {
            Py_XDECREF(f.name);
            Py_XDECREF(f.sub_capsule);
        }
    }

    const Field* find(uint64_t num) const {
        if (num < by_num.size()) {
            int16_t i = by_num[size_t(num)];
            return i < 0 ? nullptr : &fields[size_t(i)];
        }
        for (const Field& f : fields)    // a number past the index
            if (f.num == num) return &f;
        return nullptr;
    }
};

uint64_t g_native = 0;      // calls this executor answered
uint64_t g_declined = 0;    // calls it handed back to the Python walk

// ---- compiling a descriptor -----------------------------------------

PyObject* table_capsule(PyObject* desc, int depth);

inline const Table* table_in(PyObject* cap) {
    return static_cast<const Table*>(
        PyCapsule_GetPointer(cap, kCapsuleName));
}

void capsule_free(PyObject* cap) {
    delete static_cast<Table*>(PyCapsule_GetPointer(cap, kCapsuleName));
}

bool kind_of(PyObject* s, Kind* out) {
    static const struct { const char* name; Kind kind; } kKinds[] = {
        {"int32", kInt32}, {"int64", kInt64}, {"enum", kEnum},
        {"uint32", kUint32}, {"uint64", kUint64}, {"bool", kBool},
        {"sfixed64", kSfixed64}, {"fixed64", kFixed64},
        {"sfixed32", kSfixed32}, {"fixed32", kFixed32},
        {"bytes", kBytes}, {"string", kString}, {"msg", kMsg},
    };
    const char* c = PyUnicode_Check(s) ? PyUnicode_AsUTF8(s) : nullptr;
    if (!c) return false;
    for (const auto& k : kKinds)
        if (std::strcmp(c, k.name) == 0) {
            *out = k.kind;
            return true;
        }
    return false;
}

// The attribute a Msg keeps its compiled table under.
PyObject* table_key() {
    static PyObject* key = PyUnicode_InternFromString("_native_table");
    return key;
}

// One F -> one Field.  false: the field is not one this file knows;
// an exception may be set (table_capsule clears it and declines).
bool compile_field(PyObject* f, Field* out, int depth) {
    PyObject* num = PyObject_GetAttrString(f, "num");
    PyObject* name = PyObject_GetAttrString(f, "name");
    PyObject* kind = PyObject_GetAttrString(f, "kind");
    PyObject* tag = PyObject_GetAttrString(f, "tag");
    PyObject* rep = PyObject_GetAttrString(f, "repeated");
    PyObject* alw = PyObject_GetAttrString(f, "always");
    bool ok = num && name && kind && tag && rep && alw &&
              PyLong_Check(num) && PyUnicode_CheckExact(name) &&
              PyBytes_Check(tag) && PyBytes_GET_SIZE(tag) >= 1 &&
              PyBytes_GET_SIZE(tag) <= 10 && kind_of(kind, &out->kind);
    if (ok) {
        out->num = PyLong_AsUnsignedLongLong(num);
        ok = !PyErr_Occurred();
    }
    if (ok) {
        out->tag_len = uint8_t(PyBytes_GET_SIZE(tag));
        std::memcpy(out->tag, PyBytes_AS_STRING(tag), out->tag_len);
        int r = PyObject_IsTrue(rep), a = PyObject_IsTrue(alw);
        ok = r >= 0 && a >= 0;
        out->repeated = r > 0;
        out->always = a > 0;
    }
    if (ok) {
        PyUnicode_InternInPlace(&name);
        out->name = name;           // the Field owns the reference now
        name = nullptr;
    }
    Py_XDECREF(num);
    Py_XDECREF(name);
    Py_XDECREF(kind);
    Py_XDECREF(tag);
    Py_XDECREF(rep);
    Py_XDECREF(alw);
    if (!ok || out->kind != kMsg) return ok;
    PyObject* sub = PyObject_GetAttrString(f, "msg");
    if (!sub) return false;
    out->sub_capsule = table_capsule(sub, depth + 1);
    Py_DECREF(sub);
    if (out->sub_capsule) out->sub = table_in(out->sub_capsule);
    return out->sub != nullptr;
}

// The compiled table of a Msg, in its capsule (a new reference, held
// by the caller for as long as it walks the table), made on first use
// and kept on the Msg (object.__setattr__: Msg is a frozen dataclass).
// nullptr: the descriptor does not compile; any exception is cleared
// and the caller declines.  `depth` stops the compile of a descriptor
// that reaches itself; `height` holds every table to kMaxDepth levels.
PyObject* table_capsule(PyObject* desc, int depth) {
    PyObject* key = table_key();
    if (!key) return nullptr;
    PyObject* cap = PyObject_GetAttr(desc, key);
    if (cap) {
        if (PyCapsule_CheckExact(cap) && table_in(cap)) return cap;
        Py_DECREF(cap);
        PyErr_Clear();
        return nullptr;
    }
    PyErr_Clear();
    if (depth >= kMaxDepth) return nullptr;
    PyObject* fields = PyObject_GetAttrString(desc, "fields");
    PyObject* name = PyObject_GetAttrString(desc, "name");
    Table* t = new Table();
    bool ok = fields && name && PyTuple_Check(fields) &&
              PyUnicode_Check(name) && PyTuple_GET_SIZE(fields) < 32767;
    if (ok) {
        Py_INCREF(name);
        t->name = name;
        Py_ssize_t n = PyTuple_GET_SIZE(fields);
        t->fields.assign(size_t(n), Field{});
        for (Py_ssize_t i = 0; ok && i < n; i++) {
            Field& f = t->fields[size_t(i)];
            ok = compile_field(PyTuple_GET_ITEM(fields, i), &f, depth);
            // ascending and distinct, as Msg.__init__ leaves them
            ok = ok && (i == 0 || f.num > t->fields[size_t(i) - 1].num);
            if (ok && f.sub && f.sub->height >= t->height)
                t->height = f.sub->height + 1;
        }
        ok = ok && t->height <= kMaxDepth;
    }
    Py_XDECREF(fields);
    Py_XDECREF(name);
    cap = nullptr;
    if (ok) {
        uint64_t top = t->fields.empty() ? 0 : t->fields.back().num;
        t->by_num.assign(size_t(top < 1024 ? top + 1 : 1024), -1);
        for (size_t i = 0; i < t->fields.size(); i++) {
            const Field& f = t->fields[i];
            if (f.num < t->by_num.size())
                t->by_num[size_t(f.num)] = int16_t(i);
            if (f.kind == kMsg && f.always && !f.repeated)
                t->always.push_back(uint16_t(i));
        }
        cap = PyCapsule_New(t, kCapsuleName, capsule_free);
        if (cap) {
            t = nullptr;                // the capsule owns it now
            if (PyObject_GenericSetAttr(desc, key, cap) < 0)
                Py_CLEAR(cap);
        }
    }
    delete t;
    PyErr_Clear();
    return cap;
}

// ---- encode -----------------------------------------------------------

enum Status { kOk, kDecline, kError };

struct Buf {
    uint8_t inline_[512];
    uint8_t* p = inline_;
    size_t n = 0;
    size_t cap = sizeof(inline_);

    ~Buf() {
        if (p != inline_) std::free(p);
    }
    bool room(size_t more) {
        if (more <= cap - n) return true;
        size_t want = cap * 2;
        while (want - n < more) want *= 2;
        uint8_t* q = static_cast<uint8_t*>(
            p == inline_ ? std::malloc(want) : std::realloc(p, want));
        if (!q) return false;
        if (p == inline_) std::memcpy(q, inline_, n);
        p = q;
        cap = want;
        return true;
    }
    bool put(const void* src, size_t len) {
        if (!room(len)) return false;
        std::memcpy(p + n, src, len);
        n += len;
        return true;
    }
    bool varint(uint64_t u) {
        if (!room(10)) return false;
        while (u > 0x7F) {
            p[n++] = uint8_t(u) | 0x80;
            u >>= 7;
        }
        p[n++] = uint8_t(u);
        return true;
    }
};

inline size_t varint_size(uint64_t u) {
    size_t k = 1;
    while (u > 0x7F) {
        u >>= 7;
        k++;
    }
    return k;
}

Status oom() {
    PyErr_NoMemory();
    return kError;
}

Status put_varint(const Field& f, uint64_t u, Buf* out) {
    return out->put(f.tag, f.tag_len) && out->varint(u) ? kOk : oom();
}

Status put_raw(const Field& f, const void* body, size_t n, Buf* out) {
    return out->put(f.tag, f.tag_len) && out->put(body, n) ? kOk : oom();
}

// tag + length + the bytes of a bytes-like value (what bytes(v)
// gives the walk); a memoryview only where len(v) counts bytes
Status put_bytes(const Field& f, PyObject* v, bool omit_zero, Buf* out) {
    if (PyMemoryView_Check(v)) {
        const Py_buffer* mv = PyMemoryView_GET_BUFFER(v);
        if (mv->ndim != 1 || mv->itemsize != 1) return kDecline;
    } else if (!(PyBytes_Check(v) || PyByteArray_Check(v))) {
        return kDecline;
    }
    Py_buffer view;
    if (PyObject_GetBuffer(v, &view, PyBUF_SIMPLE) < 0) {
        PyErr_Clear();
        return kDecline;
    }
    Status s = kOk;
    if (!(omit_zero && view.len == 0)) {
        s = put_varint(f, uint64_t(view.len), out);
        if (s == kOk && !out->put(view.buf, size_t(view.len))) s = oom();
    }
    PyBuffer_Release(&view);
    return s;
}

// One scalar: `omit_zero` is proto3's rule for a singular field; an
// item of a repeated field is written whatever its value.
Status put_scalar(const Field& f, PyObject* v, bool omit_zero,
                  Buf* out) {
    if (f.kind == kBytes) return put_bytes(f, v, omit_zero, out);
    if (f.kind == kString) {
        if (!PyUnicode_CheckExact(v)) return kDecline;
        Py_ssize_t len;
        const char* s = PyUnicode_AsUTF8AndSize(v, &len);
        if (!s) {                       // a lone surrogate: the walk
            PyErr_Clear();              // raises UnicodeEncodeError
            return kDecline;
        }
        if (omit_zero && len == 0) return kOk;
        Status st = put_varint(f, uint64_t(len), out);
        if (st != kOk) return st;
        return out->put(s, size_t(len)) ? kOk : oom();
    }
    if (!PyLong_Check(v)) return kDecline;      // bool is an int
    uint64_t u;
    int over = 0;
    switch (f.kind) {
    case kBool: {
        int t = PyObject_IsTrue(v);
        if (t < 0) return kError;
        if (omit_zero && !t) return kOk;
        uint8_t b = uint8_t(t);
        return put_raw(f, &b, 1, out);
    }
    case kInt32: case kInt64: case kEnum:
        // int(v) & MASK64: two's complement, ten bytes when negative
        u = uint64_t(PyLong_AsLongLongAndOverflow(v, &over));
        if (over) {
            u = PyLong_AsUnsignedLongLongMask(v);
            if (u == 0) return kDecline;    // k * 2^64: not zero
        }
        if (omit_zero && u == 0) return kOk;
        return put_varint(f, u, out);
    case kSfixed64: case kSfixed32: {
        long long s = PyLong_AsLongLongAndOverflow(v, &over);
        if (over || (f.kind == kSfixed32 &&
                     (s < INT32_MIN || s > INT32_MAX)))
            return kDecline;                // struct.error is the walk's
        u = uint64_t(s);
        break;
    }
    default:        // kUint32, kUint64, kFixed64, kFixed32
        u = PyLong_AsUnsignedLongLong(v);
        if (u == uint64_t(-1) && PyErr_Occurred()) {
            PyErr_Clear();                  // negative or above 2^64 - 1
            return kDecline;
        }
        if (f.kind == kFixed32 && u > UINT32_MAX) return kDecline;
        if (f.kind == kUint32 || f.kind == kUint64)
            return omit_zero && u == 0 ? kOk : put_varint(f, u, out);
    }
    if (omit_zero && u == 0) return kOk;
    uint8_t le[8];
    size_t w = f.kind == kFixed64 || f.kind == kSfixed64 ? 8 : 4;
    for (size_t i = 0; i < w; i++) le[i] = uint8_t(u >> (8 * i));
    return put_raw(f, le, w, out);
}

Status encode_msg(const Table& t, PyObject* d, Buf* out);

// tag + length + body.  One byte is kept for the length and the body
// written behind it; a body of 128 bytes or more is moved up by the
// bytes its length needs beyond the first.
Status put_msg(const Field& f, PyObject* v, Buf* out) {
    if (!out->put(f.tag, f.tag_len) || !out->room(1)) return oom();
    size_t at = out->n++;
    Status s = encode_msg(*f.sub, v, out);
    if (s != kOk) return s;
    uint64_t len = out->n - at - 1;
    size_t extra = varint_size(len) - 1;
    if (extra) {
        if (!out->room(extra)) return oom();
        std::memmove(out->p + at + 1 + extra, out->p + at + 1,
                     size_t(len));
    }
    // written in place: the kept byte and room(extra) hold it, and
    // Buf::varint would ask for ten bytes and may move the buffer
    // with n wound back to `at`, leaving the body behind
    uint8_t* q = out->p + at;
    while (len > 0x7F) {
        *q++ = uint8_t(len) | 0x80;
        len >>= 7;
    }
    *q = uint8_t(len);
    out->n += extra;
    return kOk;
}

// `d` is the message's dict, or nullptr for the empty message an
// absent nullable=false field is written as.
Status encode_msg(const Table& t, PyObject* d, Buf* out) {
    if (d && !PyDict_CheckExact(d)) return kDecline;
    for (const Field& f : t.fields) {
        PyObject* v = d ? PyDict_GetItemWithError(d, f.name) : nullptr;
        if (!v && PyErr_Occurred()) {
            PyErr_Clear();              // a key that cannot be compared
            return kDecline;
        }
        if (!v || v == Py_None) {
            if (f.kind != kMsg || f.repeated || !f.always) continue;
            Status s = put_msg(f, nullptr, out);
            if (s != kOk) return s;
            continue;
        }
        // owned from here: a memoryview's buffer hooks may run code
        // that drops the dict's own reference
        Py_INCREF(v);
        Status s = kOk;
        if (f.repeated) {
            if (!(PyList_CheckExact(v) || PyTuple_CheckExact(v)))
                s = kDecline;
            // the size is read again every turn, for the same reason
            for (Py_ssize_t i = 0;
                 s == kOk && i < PySequence_Fast_GET_SIZE(v); i++) {
                PyObject* item = PySequence_Fast_GET_ITEM(v, i);
                Py_INCREF(item);
                s = item == Py_None ? kDecline
                    : f.kind == kMsg ? put_msg(f, item, out)
                    : put_scalar(f, item, false, out);
                Py_DECREF(item);
            }
        } else if (f.kind == kMsg) {
            s = put_msg(f, v, out);
        } else {
            s = put_scalar(f, v, true, out);
        }
        Py_DECREF(v);
        if (s != kOk) return s;
    }
    return kOk;
}

// ---- decode -----------------------------------------------------------

struct Reader {
    const uint8_t* p;
    const uint8_t* end;
};

Status fail(const char* msg) {
    PyErr_SetString(PyExc_ValueError, msg);
    return kError;
}

// proto.decode_uvarint: at most ten bytes; the tenth may carry bit 63
// only (more is the walk's to answer: it returns an int above 2^64).
inline Status read_varint(Reader* r, uint64_t* out) {
    uint64_t u = 0;
    for (int shift = 0; shift <= 63; shift += 7) {
        if (r->p >= r->end) return fail("truncated varint");
        uint8_t b = *r->p++;
        if (shift == 63 && (b & 0x7E)) return kDecline;
        u |= uint64_t(b & 0x7F) << shift;
        if (!(b & 0x80)) {
            *out = u;
            return kOk;
        }
    }
    return fail("varint too long");
}

// proto._skip: a fixed or length-delimited field that runs past the
// end ends the message without an error, as `while pos < n` does.
Status skip(Reader* r, unsigned wt) {
    uint64_t n = 0;
    switch (wt) {
    case 0:
        return read_varint(r, &n);
    case 1:
        n = 8;
        break;
    case 5:
        n = 4;
        break;
    case 2: {
        Status s = read_varint(r, &n);
        if (s != kOk) return s;
        break;
    }
    default:
        PyErr_Format(PyExc_ValueError, "cannot skip wire type %u", wt);
        return kError;
    }
    r->p = n < uint64_t(r->end - r->p) ? r->p + n : r->end;
    return kOk;
}

inline uint64_t load_le(const uint8_t* p, size_t w) {
    uint64_t u = 0;
    for (size_t i = 0; i < w; i++) u |= uint64_t(p[i]) << (8 * i);
    return u;
}

// proto._dec_scalar: the value is read by the wire type FOUND, then
// shaped by the field's kind.  A new reference in *out.
Status read_scalar(const Field& f, Reader* r, unsigned wt,
                   PyObject** out) {
    uint64_t u;
    switch (wt) {
    case 0: {
        Status s = read_varint(r, &u);
        if (s != kOk) return s;
        if (f.kind == kInt64 || f.kind == kEnum)
            *out = PyLong_FromLongLong(int64_t(u));
        else if (f.kind == kInt32)
            *out = PyLong_FromLong(int32_t(uint32_t(u)));
        else if (f.kind == kBool)
            *out = PyBool_FromLong(u != 0);
        else
            *out = PyLong_FromUnsignedLongLong(u);
        break;
    }
    case 1:
        if (r->end - r->p < 8) return fail("truncated fixed64");
        u = load_le(r->p, 8);
        r->p += 8;
        *out = f.kind == kSfixed64 ? PyLong_FromLongLong(int64_t(u))
                                   : PyLong_FromUnsignedLongLong(u);
        break;
    case 5:
        if (r->end - r->p < 4) return fail("truncated fixed32");
        u = load_le(r->p, 4);
        r->p += 4;
        *out = f.kind == kSfixed32
            ? PyLong_FromLong(int32_t(uint32_t(u)))
            : PyLong_FromUnsignedLongLong(u);
        break;
    case 2: {
        Status s = read_varint(r, &u);
        if (s != kOk) return s;
        if (u > uint64_t(r->end - r->p))
            return fail("truncated length-delimited field");
        const char* at = reinterpret_cast<const char*>(r->p);
        r->p += u;
        *out = f.kind == kString
            ? PyUnicode_DecodeUTF8(at, Py_ssize_t(u), nullptr)
            : PyBytes_FromStringAndSize(at, Py_ssize_t(u));
        break;
    }
    default:
        PyErr_Format(PyExc_ValueError, "unsupported wire type %u", wt);
        return kError;
    }
    return *out ? kOk : kError;
}

// One message between r.p and r.end -> a new dict in *out.
Status decode_msg(const Table& t, Reader r, PyObject** out) {
    PyObject* d = PyDict_New();
    if (!d) return kError;
    Status s = kOk;
    while (s == kOk && r.p < r.end) {
        uint64_t key;
        if ((s = read_varint(&r, &key)) != kOk) break;
        unsigned wt = unsigned(key & 7);
        const Field* f = t.find(key >> 3);
        if (!f) {
            s = skip(&r, wt);
            continue;
        }
        PyObject* v = nullptr;
        if (f->kind == kMsg) {
            if (wt != 2) {
                PyErr_Format(PyExc_ValueError, "%U.%U: bad wire type %u",
                             t.name, f->name, wt);
                s = kError;
                break;
            }
            uint64_t len;
            if ((s = read_varint(&r, &len)) != kOk) break;
            if (len > uint64_t(r.end - r.p)) {
                s = fail("truncated embedded message");
                break;
            }
            Reader body = {r.p, r.p + len};
            r.p += len;
            s = decode_msg(*f->sub, body, &v);
        } else {
            s = read_scalar(*f, &r, wt, &v);
        }
        if (s != kOk) break;
        if (!f->repeated) {
            if (PyDict_SetItem(d, f->name, v) < 0) s = kError;
        } else {
            PyObject* list = PyDict_GetItemWithError(d, f->name);
            if (list) {
                if (PyList_Append(list, v) < 0) s = kError;
            } else if (PyErr_Occurred()) {
                s = kError;
            } else {
                list = PyList_New(1);
                if (!list) {
                    s = kError;
                } else {
                    Py_INCREF(v);
                    PyList_SET_ITEM(list, 0, v);
                    if (PyDict_SetItem(d, f->name, list) < 0)
                        s = kError;
                    Py_DECREF(list);
                }
            }
        }
        Py_DECREF(v);
    }
    // gogoproto nullable=false: an absent sub-message reads as {}
    for (size_t k = 0; s == kOk && k < t.always.size(); k++) {
        PyObject* name = t.fields[t.always[k]].name;
        int has = PyDict_Contains(d, name);
        if (has < 0) {
            s = kError;
        } else if (!has) {
            PyObject* empty = PyDict_New();
            if (!empty || PyDict_SetItem(d, name, empty) < 0) s = kError;
            Py_XDECREF(empty);
        }
    }
    if (s != kOk) {
        Py_DECREF(d);
        return s;
    }
    *out = d;
    return kOk;
}

// ---- the module's functions -------------------------------------------

PyObject* declined() {
    g_declined++;
    Py_RETURN_NONE;
}

// wire_encode(desc, dict) -> bytes, or None when this executor
// declines and proto.py's walk must answer.
PyObject* wire_encode(PyObject*, PyObject* const* args, Py_ssize_t n) {
    if (n != 2) {
        PyErr_SetString(PyExc_TypeError, "wire_encode(desc, dict)");
        return nullptr;
    }
    PyObject* cap = table_capsule(args[0], 0);
    if (!cap) return declined();
    Buf out;
    Status s = encode_msg(*table_in(cap), args[1], &out);
    Py_DECREF(cap);
    if (s == kDecline) return declined();
    if (s == kError) return nullptr;
    g_native++;
    return PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(out.p), Py_ssize_t(out.n));
}

// wire_decode(desc, bytes) -> dict, or None when this executor
// declines; ValueError as the walk raises it on malformed bytes.
PyObject* wire_decode(PyObject*, PyObject* const* args, Py_ssize_t n) {
    if (n != 2) {
        PyErr_SetString(PyExc_TypeError, "wire_decode(desc, bytes)");
        return nullptr;
    }
    PyObject* data = args[1];
    if (!(PyBytes_Check(data) || PyByteArray_Check(data)))
        return declined();
    PyObject* cap = table_capsule(args[0], 0);
    if (!cap) return declined();
    Py_buffer view;
    if (PyObject_GetBuffer(data, &view, PyBUF_SIMPLE) < 0) {
        Py_DECREF(cap);
        return nullptr;
    }
    const uint8_t* p = static_cast<const uint8_t*>(view.buf);
    PyObject* d = nullptr;
    Status s = decode_msg(*table_in(cap), Reader{p, p + view.len}, &d);
    PyBuffer_Release(&view);
    Py_DECREF(cap);
    if (s == kDecline) return declined();
    if (s == kError) return nullptr;
    g_native++;
    return d;
}

// wire_stats() -> (calls answered here, calls declined)
PyObject* wire_stats(PyObject*, PyObject*) {
    return Py_BuildValue("KK", (unsigned long long)g_native,
                         (unsigned long long)g_declined);
}

}  // namespace
}  // namespace wire
