// Native executor of types/validation._walk_commit for the one case the
// seam's batch path spends its time in: the by-index, cache-less walk of
// a set whose keys are all ed25519, with the ignore and count rules that
// verify_commit and verify_commit_light pass.
//
// types/validation._walk_commit stays the reference and the fallback;
// validation._walk_native drives this function and decides when it
// may.  Per signature it does what the reference walk does with the
// batch path's handle, in the same order: the ignore rule, the
// validator by index (a nil key stops the walk), the sign bytes spliced
// from the commit's template for the signature's flag
// (canonical.vote_sign_bytes_template's ``heads`` and ``suf``), the
// signature's length (not 64 bytes stops the walk), the triple the
// verifier stores, the count rule and the early exit past the power
// needed.  The triples go into the caller's list, and the call returns
// when it holds ``tile`` of them, so the caller hands each tile to the
// verifier as add() would, and at every point where Python has to act:
// a flag without a template yet (the caller adds it and calls again
// with the same list), a nil key, a malformed signature, or an input it
// was not written for (DECLINE: nothing of the walk is used, the
// reference walk runs it whole).
//
// It holds the GIL throughout: every input is a Python object.
#pragma once

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <vector>

namespace commit_walk {
namespace {

// what a call ended on; validation._walk_native names the same codes
enum Status : int {
    kMore = 0,          // the list holds `tile` triples; resume at `next`
    kDone = 1,          // the end, or the early exit
    kNeedTemplate = 2,  // sigs[next]'s flag has no template in the dict
    kNilKey = 3,        // validator `next` has a nil key
    kMalformed = 4,     // sigs[next]'s signature is not 64 bytes
    kDecline = 5,       // an input this walk was not written for
};

enum Step { kSkipped, kAppended, kStop };

constexpr long kFlagAbsent = 1;     // types/vote.BLOCK_ID_FLAG_ABSENT
constexpr long kFlagCommit = 2;     // types/vote.BLOCK_ID_FLAG_COMMIT
constexpr Py_ssize_t kHeads = 23;   // field lengths 0..22
constexpr Py_ssize_t kKeyLen = 32;
constexpr Py_ssize_t kSigLen = 64;

PyObject* g_flag_name = nullptr;
PyObject* g_ts_name = nullptr;
PyObject* g_sig_name = nullptr;

bool intern_names() {
    if (g_sig_name) return true;
    g_flag_name = PyUnicode_InternFromString("block_id_flag");
    g_ts_name = PyUnicode_InternFromString("timestamp");
    g_sig_name = PyUnicode_InternFromString("signature");
    return g_flag_name && g_ts_name && g_sig_name;
}

// An int's low 64 bits (proto's two's complement: what make(ts)'s
// ``& _MASK64`` keeps) and whether the int itself is nonzero (a
// multiple of 2**64 is written as a zero varint, not left out).
bool read_int(PyObject* o, uint64_t* v, bool* nonzero) {
    if (!PyLong_Check(o)) return false;
    uint64_t x = PyLong_AsUnsignedLongLongMask(o);
    if (x == ~uint64_t(0) && PyErr_Occurred()) {
        PyErr_Clear();
        return false;
    }
    *v = x;
    if (x != 0) {
        *nonzero = true;
        return true;
    }
    int t = PyObject_IsTrue(o);
    if (t < 0) {
        PyErr_Clear();
        return false;
    }
    *nonzero = t != 0;
    return true;
}

size_t put_varint(uint8_t* p, uint64_t v) {
    size_t n = 0;
    while (v >= 0x80) {
        p[n++] = uint8_t(v | 0x80);
        v >>= 7;
    }
    p[n++] = uint8_t(v);
    return n;
}

struct Walk {
    PyObject* keys;         // tuple: 32-byte bytes or None, set order
    const char* powers;     // int64 a validator, native order
    PyObject* templates;    // dict: flag -> (heads, suf)
    int ignore;             // 0: flag is ABSENT; 1: flag is not COMMIT
    int count;              // 0: flag is COMMIT; 1: every signature
    long long tallied;
    PyObject* triples;      // the caller's list of (pub, msg, sig)
    std::vector<uint32_t> lanes;
    Status stop = kDone;
    // the template in use: a strong reference to the dict's value
    PyObject* tmpl = nullptr;
    long tmpl_flag = 0;

    ~Walk() { Py_XDECREF(tmpl); }

    Step stop_at(Status s) {
        stop = s;
        return kStop;
    }

    // the template of `flag`, checked once a lookup
    bool use_template(PyObject* flag_o, long flag, Step* out) {
        if (tmpl && flag == tmpl_flag) return true;
        PyObject* t = PyDict_GetItemWithError(templates, flag_o);
        if (!t) {
            if (PyErr_Occurred()) {
                PyErr_Clear();
                *out = stop_at(kDecline);
            } else {
                *out = stop_at(kNeedTemplate);
            }
            return false;
        }
        bool ok = PyTuple_CheckExact(t) && PyTuple_GET_SIZE(t) == 2 &&
                  PyBytes_CheckExact(PyTuple_GET_ITEM(t, 1));
        PyObject* heads = ok ? PyTuple_GET_ITEM(t, 0) : nullptr;
        ok = ok && PyTuple_CheckExact(heads) &&
             PyTuple_GET_SIZE(heads) == kHeads;
        for (Py_ssize_t k = 0; ok && k < kHeads; k++)
            ok = PyBytes_CheckExact(PyTuple_GET_ITEM(heads, k));
        if (!ok) {
            *out = stop_at(kDecline);
            return false;
        }
        Py_INCREF(t);
        Py_XSETREF(tmpl, t);
        tmpl_flag = flag;
        return true;
    }

    // one signature; `cs` is held by the caller
    Step one(PyObject* cs, Py_ssize_t i) {
        PyObject* flag_o = PyObject_GetAttr(cs, g_flag_name);
        if (!flag_o) {
            PyErr_Clear();
            return stop_at(kDecline);
        }
        long flag = PyLong_Check(flag_o) ? PyLong_AsLong(flag_o) : -1;
        if (flag == -1 && (!PyLong_Check(flag_o) || PyErr_Occurred())) {
            PyErr_Clear();
            Py_DECREF(flag_o);
            return stop_at(kDecline);
        }
        if (ignore == 0 ? flag == kFlagAbsent : flag != kFlagCommit) {
            Py_DECREF(flag_o);
            return kSkipped;
        }
        if (i >= PyTuple_GET_SIZE(keys)) {
            Py_DECREF(flag_o);
            return stop_at(kDecline);
        }
        PyObject* key = PyTuple_GET_ITEM(keys, i);
        if (key == Py_None) {
            Py_DECREF(flag_o);
            return stop_at(kNilKey);
        }
        if (!PyBytes_CheckExact(key) || PyBytes_GET_SIZE(key) != kKeyLen) {
            Py_DECREF(flag_o);
            return stop_at(kDecline);
        }
        Step out = kSkipped;
        bool have = use_template(flag_o, flag, &out);
        Py_DECREF(flag_o);
        if (!have) return out;

        // the timestamp field's body: make(ts) of canonical.py
        uint8_t field[22];
        size_t n = 0;
        PyObject* ts = PyObject_GetAttr(cs, g_ts_name);
        if (!ts) {
            PyErr_Clear();
            return stop_at(kDecline);
        }
        uint64_t secs = 0, nanos = 0;
        bool secs_nz = false, nanos_nz = false;
        bool ok = PyTuple_Check(ts) && PyTuple_GET_SIZE(ts) == 2 &&
                  read_int(PyTuple_GET_ITEM(ts, 0), &secs, &secs_nz) &&
                  read_int(PyTuple_GET_ITEM(ts, 1), &nanos, &nanos_nz);
        Py_DECREF(ts);
        if (!ok) return stop_at(kDecline);
        if (secs_nz) {
            field[n++] = 0x08;
            n += put_varint(field + n, secs);
        }
        if (nanos_nz) {
            field[n++] = 0x10;
            n += put_varint(field + n, nanos);
        }

        PyObject* sig = PyObject_GetAttr(cs, g_sig_name);
        if (!sig) {
            PyErr_Clear();
            return stop_at(kDecline);
        }
        if (!PyBytes_CheckExact(sig)) {
            Py_DECREF(sig);
            return stop_at(kDecline);
        }
        if (PyBytes_GET_SIZE(sig) != kSigLen) {
            Py_DECREF(sig);
            return stop_at(kMalformed);
        }

        PyObject* head = PyTuple_GET_ITEM(PyTuple_GET_ITEM(tmpl, 0),
                                          Py_ssize_t(n));
        PyObject* suf = PyTuple_GET_ITEM(tmpl, 1);
        Py_ssize_t hl = PyBytes_GET_SIZE(head);
        Py_ssize_t sl = PyBytes_GET_SIZE(suf);
        PyObject* msg = PyBytes_FromStringAndSize(
            nullptr, hl + Py_ssize_t(n) + sl);
        PyObject* triple = msg ? PyTuple_New(3) : nullptr;
        if (!triple) {
            PyErr_Clear();
            Py_XDECREF(msg);
            Py_DECREF(sig);
            return stop_at(kDecline);
        }
        char* p = PyBytes_AS_STRING(msg);
        std::memcpy(p, PyBytes_AS_STRING(head), size_t(hl));
        std::memcpy(p + hl, field, n);
        std::memcpy(p + hl + n, PyBytes_AS_STRING(suf), size_t(sl));
        Py_INCREF(key);
        PyTuple_SET_ITEM(triple, 0, key);
        PyTuple_SET_ITEM(triple, 1, msg);
        PyTuple_SET_ITEM(triple, 2, sig);
        int rc = PyList_Append(triples, triple);
        Py_DECREF(triple);
        if (rc < 0) {
            PyErr_Clear();
            return stop_at(kDecline);
        }
        lanes.push_back(uint32_t(i));
        if (count == 1 || flag == kFlagCommit) {
            int64_t power;
            std::memcpy(&power, powers + 8 * i, 8);
            tallied += power;
        }
        return kAppended;
    }
};

// commit_walk(sigs, start, keys, powers, templates, ignore, count,
//             needed, tallied, count_all, triples, tile)
//   -> (status, next, tallied, lanes)
// ``triples`` is the caller's list, appended to; ``lanes`` holds the
// commit index of each triple this call appended, as a native uint32.
PyObject* walk(PyObject*, PyObject* args) {
    PyObject *sigs, *keys, *templates, *triples;
    Py_ssize_t start, tile, powers_len;
    const char* powers;
    int ignore, count, count_all;
    long long needed, tallied;
    if (!PyArg_ParseTuple(args, "O!nO!y#O!iiLLpO!n", &PyList_Type, &sigs,
                          &start, &PyTuple_Type, &keys, &powers,
                          &powers_len, &PyDict_Type, &templates, &ignore,
                          &count, &needed, &tallied, &count_all,
                          &PyList_Type, &triples, &tile))
        return nullptr;
    if ((ignore != 0 && ignore != 1) || (count != 0 && count != 1) ||
        tile < 1 || start < 0 ||
        powers_len != 8 * PyTuple_GET_SIZE(keys)) {
        PyErr_SetString(PyExc_ValueError, "commit_walk: bad arguments");
        return nullptr;
    }
    if (!intern_names()) return nullptr;
    Walk w{keys, powers, templates, ignore, count, tallied, triples, {}};
    w.lanes.reserve(size_t(tile));
    Py_ssize_t i = start;
    for (; i < PyList_GET_SIZE(sigs); i++) {
        PyObject* cs = PyList_GET_ITEM(sigs, i);
        Py_INCREF(cs);
        Step s = w.one(cs, i);
        Py_DECREF(cs);
        if (s == kStop) break;
        if (s == kSkipped) continue;
        if (!count_all && w.tallied > needed) {
            w.stop = kDone;
            i++;
            break;
        }
        if (PyList_GET_SIZE(w.triples) >= tile) {
            w.stop = kMore;
            i++;
            break;
        }
    }
    PyObject* lanes = PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(w.lanes.data()),
        Py_ssize_t(w.lanes.size() * sizeof(uint32_t)));
    if (!lanes) return nullptr;
    return Py_BuildValue("(inLN)", int(w.stop), i, w.tallied, lanes);
}

}  // namespace
}  // namespace commit_walk
