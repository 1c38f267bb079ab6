// cometbft_tpu._native — C++ fast paths for the host runtime.
//
// Reference parity note: the reference engine is Go with one native
// dep (blst); this build keeps the hot host-side hashing in C++
// instead.  Implements the RFC-6962-style merkle tree of
// crypto/merkle/tree.go (leaf prefix 0x00, inner prefix 0x01,
// getSplitPoint recursion) and batch SHA-256 for tx/part hashing —
// the (f) hot loop in the survey's hot-path list.
//
// Built by cometbft_tpu/crypto/_native_loader.py (g++ -O3); the
// Python implementations remain the fallback when no compiler is
// available.
#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <pthread.h>
#include <sched.h>
#include <signal.h>
#include <time.h>

#include <algorithm>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <thread>
#include <vector>

#include "sha256.hpp"
#include "sha512.hpp"
#include "sha512_mb.hpp"
#include "ed25519_msm.hpp"
#include "bls12381.hpp"
#include "chacha20poly1305.hpp"
#include "commit_walk.hpp"
#include "wire_codec.hpp"

namespace {

constexpr uint8_t kLeafPrefix = 0x00;
constexpr uint8_t kInnerPrefix = 0x01;

struct Slice {
    const uint8_t* p;
    Py_ssize_t n;
};

size_t split_point(size_t n) {
    // largest power of two strictly less than n (tree.go:89)
    size_t b = 1;
    while (b * 2 < n) b *= 2;
    return b;
}

void inner_hash(const uint8_t l[32], const uint8_t r[32],
                uint8_t out[32]) {
    sha256::Ctx c;
    sha256::init(&c);
    sha256::update(&c, &kInnerPrefix, 1);
    sha256::update(&c, l, 32);
    sha256::update(&c, r, 32);
    sha256::final(&c, out);
}

void tree_hash(const std::vector<Slice>& items, size_t lo, size_t hi,
               uint8_t out[32]) {
    size_t n = hi - lo;
    if (n == 1) {
        sha256::hash_prefixed(kLeafPrefix, items[lo].p,
                              size_t(items[lo].n), out);
        return;
    }
    size_t k = split_point(n);
    uint8_t left[32], right[32];
    tree_hash(items, lo, lo + k, left);
    tree_hash(items, lo + k, hi, right);
    inner_hash(left, right, out);
}

bool collect(PyObject* seq_in, std::vector<Slice>* items,
             PyObject** fast_out) {
    PyObject* fast = PySequence_Fast(seq_in, "expected a sequence");
    if (!fast) return false;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    items->reserve(size_t(n));
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject* it = PySequence_Fast_GET_ITEM(fast, i);
        char* buf;
        Py_ssize_t len;
        if (PyBytes_AsStringAndSize(it, &buf, &len) < 0) {
            Py_DECREF(fast);
            return false;
        }
        items->push_back(
            {reinterpret_cast<const uint8_t*>(buf), len});
    }
    *fast_out = fast;
    return true;
}

PyObject* merkle_root(PyObject*, PyObject* arg) {
    std::vector<Slice> items;
    PyObject* fast;
    if (!collect(arg, &items, &fast)) return nullptr;
    uint8_t out[32];
    if (items.empty()) {
        sha256::hash(nullptr, 0, out);
    } else {
        tree_hash(items, 0, items.size(), out);
    }
    Py_DECREF(fast);
    return PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(out), 32);
}

PyObject* leaf_hashes(PyObject*, PyObject* arg) {
    // concatenated 32-byte RFC-6962 leaf hashes
    std::vector<Slice> items;
    PyObject* fast;
    if (!collect(arg, &items, &fast)) return nullptr;
    PyObject* out =
        PyBytes_FromStringAndSize(nullptr, Py_ssize_t(items.size()) * 32);
    if (!out) {
        Py_DECREF(fast);
        return nullptr;
    }
    uint8_t* p =
        reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    for (size_t i = 0; i < items.size(); i++)
        sha256::hash_prefixed(kLeafPrefix, items[i].p,
                              size_t(items[i].n), p + i * 32);
    Py_DECREF(fast);
    return out;
}

PyObject* sha256_many(PyObject*, PyObject* arg) {
    // concatenated plain SHA-256 digests (tx hashing)
    std::vector<Slice> items;
    PyObject* fast;
    if (!collect(arg, &items, &fast)) return nullptr;
    PyObject* out =
        PyBytes_FromStringAndSize(nullptr, Py_ssize_t(items.size()) * 32);
    if (!out) {
        Py_DECREF(fast);
        return nullptr;
    }
    uint8_t* p =
        reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    for (size_t i = 0; i < items.size(); i++)
        sha256::hash(items[i].p, size_t(items[i].n), p + i * 32);
    Py_DECREF(fast);
    return out;
}

PyObject* sha512_many(PyObject*, PyObject* arg) {
    // concatenated 64-byte SHA-512 digests
    std::vector<Slice> items;
    PyObject* fast;
    if (!collect(arg, &items, &fast)) return nullptr;
    PyObject* out =
        PyBytes_FromStringAndSize(nullptr, Py_ssize_t(items.size()) * 64);
    if (!out) {
        Py_DECREF(fast);
        return nullptr;
    }
    uint8_t* p = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    for (size_t i = 0; i < items.size(); i++)
        sha512::hash(items[i].p, size_t(items[i].n), p + i * 64);
    Py_DECREF(fast);
    return out;
}

PyObject* ed25519_kscalars(PyObject*, PyObject* arg) {
    // per item: SHA-512(item) reduced mod the ed25519 group order L,
    // as concatenated 32-byte little-endian scalars (the batch
    // verifier's k = H(R || A || msg) host-prep hot loop)
    std::vector<Slice> items;
    PyObject* fast;
    if (!collect(arg, &items, &fast)) return nullptr;
    PyObject* out =
        PyBytes_FromStringAndSize(nullptr, Py_ssize_t(items.size()) * 32);
    if (!out) {
        Py_DECREF(fast);
        return nullptr;
    }
    uint8_t* p = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    uint8_t digest[64];
    for (size_t i = 0; i < items.size(); i++) {
        sha512::hash(items[i].p, size_t(items[i].n), digest);
        sha512::reduce_mod_l(digest, p + i * 32);
    }
    Py_DECREF(fast);
    return out;
}

// ed25519_prep(items, m, b_bytes, identity_bytes) -> (wire, pre_bad)
// items: sequence of (pub, msg, sig) byte tuples; m: padded lane
// count (>= len(items)).  Outputs are numpy-ready buffers:
//   wire: [m, 192] uint8, ONE buffer that goes to the device in one
//     transfer (1 byte per element — a quarter of the int32 device
//     layouts; the int32 transpose/cast runs on-device).  A lane is a
//     row of WIRE_LANE bytes:
//       [0, 32)    A (padding lanes = B)
//       [32, 64)   R (padding lanes = identity)
//       [64, 128)  4-bit windows of S
//       [128, 192) 4-bit windows of k
//     (ops/ed25519_jax.wire_views names the same four column ranges)
//   pre_bad: [m] uint8 (1 = malformed or non-canonical S)
// This is the batch verifier's entire host prep: pointers are
// extracted under the GIL (cheap), then the SHA-512 / window loop
// runs GIL-free across hardware threads — the budget (BASELINE:
// < 5 ms e2e at 10k sigs) leaves < 3 ms for all host work, and
// single-threaded SHA-512 alone is ~9 ms at 10k.
namespace prep {

// one lane of the wire buffer: A | R | S windows | k windows
constexpr Py_ssize_t WIRE_LANE = 192;
constexpr Py_ssize_t WIRE_R = 32, WIRE_S = 64, WIRE_K = 128;

struct ItemRef {
    const uint8_t* pub;
    const uint8_t* msg;
    size_t msglen;
    const uint8_t* sig;
    bool bad;
};

// L little-endian, for the canonical-S check
static const uint8_t L_LE[32] = {
    0xed, 0xd3, 0xf5, 0x5c, 0x1a, 0x63, 0x12, 0x58,
    0xd6, 0x9c, 0xf7, 0xa2, 0xde, 0xf9, 0xde, 0x14,
    0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00,
    0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x10,
};

inline void write_windows(uint8_t* row, const uint8_t le[32]) {
    for (int b = 0; b < 32; b++) {
        row[2 * b] = le[b] & 0x0F;
        row[2 * b + 1] = le[b] >> 4;
    }
}

inline void k_windows_from_digest(const uint8_t digest[64],
                                  uint8_t* wire, Py_ssize_t lane) {
    uint8_t k_le[32];
    sha512::reduce_mod_l(digest, k_le);
    write_windows(wire + lane * WIRE_LANE + WIRE_K, k_le);
}

#if COMETBFT_SHA512MB_X86
// pending 8-lane group of equal-block-count messages for the
// multi-buffer hasher
struct KGroup {
    size_t nblocks = 0;
    int n = 0;
    Py_ssize_t lane[8];
    const ItemRef* item[8];
};

inline void flush_group(KGroup& g, std::vector<uint8_t>& scratch,
                        uint8_t* wire) {
    if (g.n == 0) return;
    size_t slot = g.nblocks * 128;
    scratch.assign(slot * 8, 0);
    const uint8_t* base[8];
    for (int l = 0; l < 8; l++) {
        int src = l < g.n ? l : 0;      // pad group with lane 0
        if (l < g.n) {
            uint8_t* buf = scratch.data() + size_t(l) * slot;
            const ItemRef* it = g.item[l];
            std::memcpy(buf, it->sig, 32);
            std::memcpy(buf + 32, it->pub, 32);
            std::memcpy(buf + 64, it->msg, it->msglen);
            sha512mb::write_padding(buf, 64 + it->msglen,
                                    g.nblocks);
            base[l] = buf;
        } else {
            base[l] = scratch.data() + size_t(src) * slot;
        }
    }
    uint8_t digests[8][64];
    sha512mb::hash8(base, g.nblocks, digests);
    for (int l = 0; l < g.n; l++)
        k_windows_from_digest(digests[l], wire, g.lane[l]);
    g.n = 0;
}
#endif

// phase 2 worker: lanes [lo, hi) — canonical-S, row copies, SHA-512
// (8-way multi-buffer where AVX-512 is present), item-major windows,
// each lane into its own row of the wire buffer
void lanes(const ItemRef* refs, Py_ssize_t lo, Py_ssize_t hi,
           uint8_t* wire, uint8_t* bad_p) {
#if COMETBFT_SHA512MB_X86
    const bool use_mb = sha512mb::available();
    // groups keyed by block count (messages in one batch are nearly
    // always uniform-length vote sign-bytes, so this stays tiny)
    std::vector<KGroup> groups;
    std::vector<uint8_t> scratch;
#endif
    for (Py_ssize_t i = lo; i < hi; i++) {
        const ItemRef& it = refs[i];
        if (it.bad) {
            bad_p[i] = 1;
            continue;
        }
        const uint8_t* s_le = it.sig + 32;
        bool lt = false, gt = false;
        for (int b = 31; b >= 0; b--) {
            if (s_le[b] < L_LE[b]) { lt = true; break; }
            if (s_le[b] > L_LE[b]) { gt = true; break; }
        }
        if (!lt || gt) {     // s >= L: non-canonical
            bad_p[i] = 1;
            continue;
        }
        uint8_t* row = wire + i * WIRE_LANE;
        std::memcpy(row, it.pub, 32);
        std::memcpy(row + WIRE_R, it.sig, 32);
        write_windows(row + WIRE_S, s_le);
#if COMETBFT_SHA512MB_X86
        if (use_mb) {
            size_t nb = sha512mb::block_count(64 + it.msglen);
            if (nb <= 128) {            // > 16 KiB msgs go scalar
                KGroup* g = nullptr;
                for (auto& cand : groups)
                    if (cand.nblocks == nb) { g = &cand; break; }
                if (!g) {
                    groups.emplace_back();
                    g = &groups.back();
                    g->nblocks = nb;
                }
                g->lane[g->n] = i;
                g->item[g->n] = &it;
                if (++g->n == 8) flush_group(*g, scratch, wire);
                continue;
            }
        }
#endif
        // scalar fallback: k = SHA-512(R || A || msg) mod L
        sha512::Ctx c;
        sha512::init(&c);
        sha512::update(&c, it.sig, 32);
        sha512::update(&c, it.pub, 32);
        sha512::update(&c, it.msg, it.msglen);
        uint8_t digest[64];
        sha512::final(&c, digest);
        k_windows_from_digest(digest, wire, i);
    }
#if COMETBFT_SHA512MB_X86
    for (auto& g : groups) flush_group(g, scratch, wire);
#endif
}

// One prep: what phase 1 borrowed from the Python objects and what
// phase 2 fills.  A PrepHandle owns it; the worker holds a plain
// pointer to it while it is queued or running, and the handle does
// not go before the job is its own again.
struct Worker;

struct Job {
    PyObject* fast = nullptr;           // the items, kept alive
    std::vector<PyObject*> fits;        // each item as a fast sequence
    std::vector<ItemRef> refs;
    Py_ssize_t n = 0, m = 0;
    uint8_t b[32], id[32];
    PyObject* wire_out = nullptr;
    PyObject* bad_out = nullptr;
    uint8_t* wire_p = nullptr;
    uint8_t* bad_p = nullptr;
    enum State { QUEUED, RUNNING, DONE };
    State state = QUEUED;               // under its worker's mutex
    Worker* worker = nullptr;           // where it was posted, if at all
    int64_t t0_ns = 0, t1_ns = 0;       // phase 2, CLOCK_MONOTONIC
};

inline int64_t mono_ns() {
    // the clock of Python's time.monotonic_ns(), so that a span can
    // be recorded from these readings (libs/tracing.record_span)
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

// phase 2: no Python object is touched and the GIL is not needed
void run(Job* j) {
    j->t0_ns = mono_ns();
    // padding defaults (windows of unwritten lanes must be zero)
    std::memset(j->wire_p, 0, size_t(WIRE_LANE) * size_t(j->m));
    std::memset(j->bad_p, 0, size_t(j->m));
    for (Py_ssize_t i = 0; i < j->m; i++) {
        uint8_t* row = j->wire_p + i * WIRE_LANE;
        std::memcpy(row, j->b, 32);
        std::memcpy(row + WIRE_R, j->id, 32);
    }
    lanes(j->refs.data(), 0, j->n, j->wire_p, j->bad_p);
    j->t1_ns = mono_ns();
}

// The one persistent native thread that runs posted jobs in order.
// It never takes the GIL.  Started at the first post; never joined
// (it sleeps on its queue until the process goes); a forked child,
// which has no such thread, starts its own at its first post and
// runs what its parent had posted on the thread that asks for it.
struct Worker {
    std::mutex mu;
    std::condition_variable work, done;
    std::deque<Job*> queue;

    void loop() {
        std::unique_lock<std::mutex> lk(mu);
        for (;;) {
            work.wait(lk, [&] { return !queue.empty(); });
            Job* j = queue.front();
            queue.pop_front();
            j->state = Job::RUNNING;
            lk.unlock();
            run(j);
            lk.lock();
            j->state = Job::DONE;
            done.notify_all();
        }
    }
};

Worker* g_worker = nullptr;             // under the GIL

// The kernel starts a thread on its creator's CPU and, in a KVM guest,
// goes on waking it there (a halted vCPU counts as preempted, so the
// wake-up finds no idle CPU to prefer), where it runs each prep in
// its caller's place: begin() returned when the prep was DONE, 270 us
// for 22 (CPU sandbox, PR 36).  So the thread moves off that CPU
// once, at its start, and is then as free as before; from there on it
// wakes where it last ran.
void leave_cpu(int cpu) {
    cpu_set_t allowed;
    if (cpu < 0 || sched_getaffinity(0, sizeof allowed, &allowed) != 0 ||
        !CPU_ISSET(cpu, &allowed) || CPU_COUNT(&allowed) < 2)
        return;
    cpu_set_t others = allowed;
    CPU_CLR(cpu, &others);
    if (sched_setaffinity(0, sizeof others, &others) == 0)
        sched_setaffinity(0, sizeof allowed, &allowed);
}

void forget_worker_in_child() { g_worker = nullptr; }

Worker* worker() {
    if (g_worker) return g_worker;
    static bool registered = false;
    if (!registered) {
        pthread_atfork(nullptr, nullptr, forget_worker_in_child);
        registered = true;
    }
    Worker* w = new Worker;
    // signals stay with the interpreter's threads
    sigset_t all, old;
    sigfillset(&all);
    pthread_sigmask(SIG_SETMASK, &all, &old);
    int creator_cpu = sched_getcpu();
    std::thread([w, creator_cpu] {
        leave_cpu(creator_cpu);
        w->loop();
    }).detach();
    pthread_sigmask(SIG_SETMASK, &old, nullptr);
    return g_worker = w;
}

// Make the job its owner's again: take it off the queue if no thread
// has begun it, else wait until it is done.  ``reading`` is result():
// an unbegun job is run here, and both that and the wait release the
// GIL; a handle that is being freed runs nothing and keeps the GIL for
// the millisecond a running prep has left.  Returns the nanoseconds
// this took; 0 for a job that was done.
int64_t settle(Job* j, bool reading) {
    int64_t t0 = mono_ns();
    Worker* w = j->worker;
    if (w && w == g_worker) {
        std::unique_lock<std::mutex> lk(w->mu);
        if (j->state == Job::RUNNING) {
            PyThreadState* ts = reading ? PyEval_SaveThread() : nullptr;
            w->done.wait(lk, [&] { return j->state == Job::DONE; });
            lk.unlock();
            if (ts) PyEval_RestoreThread(ts);
            return mono_ns() - t0;
        }
        if (j->state == Job::QUEUED) {
            auto& q = w->queue;
            q.erase(std::find(q.begin(), q.end(), j));
        }
    }
    // done; or never posted, taken back, or posted to a thread this
    // process (a forked child) does not have
    j->worker = nullptr;
    if (j->state == Job::DONE || !reading) return 0;
    PyThreadState* ts = PyEval_SaveThread();
    run(j);
    PyEval_RestoreThread(ts);
    j->state = Job::DONE;
    return mono_ns() - t0;
}

}  // namespace prep

struct PrepHandle {
    PyObject_HEAD
    prep::Job* job;
};

void prep_handle_free_refs(prep::Job* j) {
    for (PyObject* fit : j->fits) Py_DECREF(fit);
    j->fits.clear();
    Py_CLEAR(j->fast);
    Py_CLEAR(j->wire_out);
    Py_CLEAR(j->bad_out);
}

void prep_handle_dealloc(PyObject* self) {
    prep::Job* j = reinterpret_cast<PrepHandle*>(self)->job;
    if (j) {
        // dropped unread: unqueue it, or let a running prep finish
        prep::settle(j, /*reading=*/false);
        prep_handle_free_refs(j);
        delete j;
    }
    Py_TYPE(self)->tp_free(self);
}

// result() -> (wire, pre_bad, start_ns, elapsed_ns, waited_ns): the
// two buffers of ed25519_prep, the prep's own clock readings
// (CLOCK_MONOTONIC, time.monotonic_ns's) and how long this call
// waited for it, 0 when it had finished.  Once.
PyObject* prep_handle_result(PyObject* self, PyObject*) {
    prep::Job* j = reinterpret_cast<PrepHandle*>(self)->job;
    if (!j || !j->wire_out) {
        PyErr_SetString(PyExc_RuntimeError,
                        "prep result already taken");
        return nullptr;
    }
    int64_t waited = prep::settle(j, /*reading=*/true);
    PyObject* out = Py_BuildValue(
        "(OOLLL)", j->wire_out, j->bad_out, (long long)j->t0_ns,
        (long long)(j->t1_ns - j->t0_ns), (long long)waited);
    prep_handle_free_refs(j);
    return out;
}

PyMethodDef kPrepHandleMethods[] = {
    {"result", prep_handle_result, METH_NOARGS,
     "wait (GIL released) -> (wire, pre_bad, start_ns, elapsed_ns, "
     "waited_ns)"},
    {nullptr, nullptr, 0, nullptr},
};

PyTypeObject kPrepHandleType = {PyVarObject_HEAD_INIT(nullptr, 0)};

bool prep_handle_type_ready() {
    kPrepHandleType.tp_name = "_native.PrepHandle";
    kPrepHandleType.tp_basicsize = sizeof(PrepHandle);
    kPrepHandleType.tp_flags = Py_TPFLAGS_DEFAULT;
    kPrepHandleType.tp_dealloc = prep_handle_dealloc;
    kPrepHandleType.tp_methods = kPrepHandleMethods;
    kPrepHandleType.tp_doc = "an ed25519 host prep in flight";
    return PyType_Ready(&kPrepHandleType) == 0;
}

// phase 1 (GIL held): borrow data pointers out of the Python objects,
// kept alive by the job's references until its result is taken or
// its handle freed; ``post`` hands phase 2 to the native thread.
PyObject* prep_begin(PyObject* args, bool post) {
    PyObject* seq_in;
    Py_ssize_t m;
    const char* b_bytes;
    Py_ssize_t b_len;
    const char* id_bytes;
    Py_ssize_t id_len;
    if (!PyArg_ParseTuple(args, "Ony#y#", &seq_in, &m, &b_bytes,
                          &b_len, &id_bytes, &id_len))
        return nullptr;
    if (b_len != 32 || id_len != 32) {
        PyErr_SetString(PyExc_ValueError, "constants must be 32 bytes");
        return nullptr;
    }
    PyObject* fast = PySequence_Fast(seq_in, "expected a sequence");
    if (!fast) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    if (n > m) {
        Py_DECREF(fast);
        PyErr_SetString(PyExc_ValueError, "m < len(items)");
        return nullptr;
    }
    PrepHandle* h = PyObject_New(PrepHandle, &kPrepHandleType);
    if (!h) {
        Py_DECREF(fast);
        return nullptr;
    }
    prep::Job* j = h->job = new prep::Job;
    j->fast = fast;
    j->n = n;
    j->m = m;
    std::memcpy(j->b, b_bytes, 32);
    std::memcpy(j->id, id_bytes, 32);
    j->wire_out = PyBytes_FromStringAndSize(nullptr,
                                            prep::WIRE_LANE * m);
    j->bad_out = PyBytes_FromStringAndSize(nullptr, m);
    if (!j->wire_out || !j->bad_out) {
        Py_DECREF(h);
        return nullptr;
    }
    j->wire_p = reinterpret_cast<uint8_t*>(
        PyBytes_AS_STRING(j->wire_out));
    j->bad_p = reinterpret_cast<uint8_t*>(
        PyBytes_AS_STRING(j->bad_out));
    j->refs.resize(static_cast<size_t>(n));
    j->fits.reserve(size_t(n));
    for (Py_ssize_t i = 0; i < n; i++) {
        prep::ItemRef& ref = j->refs[size_t(i)];
        ref.bad = true;
        PyObject* it = PySequence_Fast_GET_ITEM(fast, i);
        PyObject* fit = PySequence_Fast(it, "item must be a tuple");
        if (!fit || PySequence_Fast_GET_SIZE(fit) != 3) {
            PyErr_Clear();
            Py_XDECREF(fit);
            continue;
        }
        j->fits.push_back(fit);
        char *pub, *msg, *sig;
        Py_ssize_t publen, msglen, siglen;
        if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 0),
                                    &pub, &publen) < 0 ||
            PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 1),
                                    &msg, &msglen) < 0 ||
            PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 2),
                                    &sig, &siglen) < 0) {
            PyErr_Clear();
            continue;
        }
        if (publen != 32 || siglen != 64) continue;
        ref.pub = reinterpret_cast<uint8_t*>(pub);
        ref.msg = reinterpret_cast<uint8_t*>(msg);
        ref.msglen = size_t(msglen);
        ref.sig = reinterpret_cast<uint8_t*>(sig);
        ref.bad = false;
    }
    if (post) {
        prep::Worker* w = j->worker = prep::worker();
        {
            std::lock_guard<std::mutex> lk(w->mu);
            w->queue.push_back(j);
        }
        w->work.notify_one();
    }
    return reinterpret_cast<PyObject*>(h);
}

// ed25519_prep_begin(items, m, b_bytes, identity_bytes) -> PrepHandle:
// phase 1 here, phase 2 on the native thread while the caller goes on
PyObject* ed25519_prep_begin(PyObject*, PyObject* args) {
    return prep_begin(args, /*post=*/true);
}

// the same prep for a caller with nothing to do meanwhile: begun
// without a post, so result() runs phase 2 on this thread, GIL
// released, and no thread is woken for it
PyObject* ed25519_prep(PyObject*, PyObject* args) {
    PyObject* h = prep_begin(args, /*post=*/false);
    if (!h) return nullptr;
    PyObject* res = prep_handle_result(h, nullptr);
    Py_DECREF(h);
    if (!res) return nullptr;
    PyObject* out = PyTuple_GetSlice(res, 0, 2);
    Py_DECREF(res);
    return out;
}

// --- BLS12-381 (see native/bls12381.hpp) -----------------------------------
// Point wire format between python and C: raw affine coordinates,
// big-endian —  G1: 96B x||y;  G2: 192B x0||x1||y0||y1;  b"" = infinity.

bool parse_g1(PyObject* obj, bls::G1* out) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(obj, &buf, &len) < 0) return false;
    const uint8_t* b = reinterpret_cast<uint8_t*>(buf);
    if (len == 0) {
        out->inf = true;
        return true;
    }
    if (len != 96) {
        PyErr_SetString(PyExc_ValueError, "bad G1 length");
        return false;
    }
    out->inf = false;
    if (!bls::fp_from_be48(b, &out->x) ||
        !bls::fp_from_be48(b + 48, &out->y)) {
        PyErr_SetString(PyExc_ValueError, "G1 coordinate >= p");
        return false;
    }
    return true;
}

bool parse_g2(PyObject* obj, bls::G2* out) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(obj, &buf, &len) < 0) return false;
    const uint8_t* b = reinterpret_cast<uint8_t*>(buf);
    if (len == 0) {
        out->inf = true;
        return true;
    }
    if (len != 192) {
        PyErr_SetString(PyExc_ValueError, "bad G2 length");
        return false;
    }
    out->inf = false;
    if (!bls::fp_from_be48(b, &out->x.c0) ||
        !bls::fp_from_be48(b + 48, &out->x.c1) ||
        !bls::fp_from_be48(b + 96, &out->y.c0) ||
        !bls::fp_from_be48(b + 144, &out->y.c1)) {
        PyErr_SetString(PyExc_ValueError, "G2 coordinate >= p");
        return false;
    }
    return true;
}

PyObject* g1_bytes(const bls::G1& p) {
    if (p.inf) return PyBytes_FromStringAndSize("", 0);
    uint8_t out[96];
    bls::fp_to_be48(p.x, out);
    bls::fp_to_be48(p.y, out + 48);
    return PyBytes_FromStringAndSize(
        reinterpret_cast<char*>(out), 96);
}

PyObject* g2_bytes(const bls::G2& p) {
    if (p.inf) return PyBytes_FromStringAndSize("", 0);
    uint8_t out[192];
    bls::fp_to_be48(p.x.c0, out);
    bls::fp_to_be48(p.x.c1, out + 48);
    bls::fp_to_be48(p.y.c0, out + 96);
    bls::fp_to_be48(p.y.c1, out + 144);
    return PyBytes_FromStringAndSize(
        reinterpret_cast<char*>(out), 192);
}

PyObject* bls_pairings_product_is_one(PyObject*, PyObject* arg) {
    PyObject* fast = PySequence_Fast(arg, "expected a sequence");
    if (!fast) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    std::vector<bls::Pair> pairs;
    pairs.reserve(size_t(n));
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject* it = PySequence_Fast_GET_ITEM(fast, i);
        PyObject* fit = PySequence_Fast(it, "pair must be a tuple");
        if (!fit || PySequence_Fast_GET_SIZE(fit) != 2) {
            Py_XDECREF(fit);
            Py_DECREF(fast);
            if (!PyErr_Occurred())
                PyErr_SetString(PyExc_ValueError, "pair must have 2 items");
            return nullptr;
        }
        bls::Pair pr;
        if (!parse_g1(PySequence_Fast_GET_ITEM(fit, 0), &pr.p) ||
            !parse_g2(PySequence_Fast_GET_ITEM(fit, 1), &pr.q)) {
            Py_DECREF(fit);
            Py_DECREF(fast);
            return nullptr;
        }
        pairs.push_back(pr);
        Py_DECREF(fit);
    }
    Py_DECREF(fast);
    bool ok;
    Py_BEGIN_ALLOW_THREADS
    ok = bls::pairings_product_is_one(pairs);
    Py_END_ALLOW_THREADS
    return PyBool_FromLong(ok);
}

PyObject* bls_selftest(PyObject*, PyObject*) {
    bool ok;
    Py_BEGIN_ALLOW_THREADS
    ok = bls::selftest() && bls::selftest_psi();
    Py_END_ALLOW_THREADS
    return PyBool_FromLong(ok);
}

PyObject* bls_g1_in_subgroup(PyObject*, PyObject* arg) {
    bls::G1 p;
    if (!parse_g1(arg, &p)) return nullptr;
    bool ok;
    Py_BEGIN_ALLOW_THREADS
    ok = bls::g1_in_subgroup(p);
    Py_END_ALLOW_THREADS
    return PyBool_FromLong(ok);
}

PyObject* bls_g2_in_subgroup(PyObject*, PyObject* arg) {
    bls::G2 p;
    if (!parse_g2(arg, &p)) return nullptr;
    bool ok;
    Py_BEGIN_ALLOW_THREADS
    ok = bls::g2_in_subgroup(p);
    Py_END_ALLOW_THREADS
    return PyBool_FromLong(ok);
}

PyObject* bls_hash_to_g2(PyObject*, PyObject* args) {
    const char* msg;
    Py_ssize_t msg_len;
    const char* dst;
    Py_ssize_t dst_len;
    if (!PyArg_ParseTuple(args, "y#y#", &msg, &msg_len, &dst,
                          &dst_len))
        return nullptr;
    if (dst_len > 255) {
        PyErr_SetString(PyExc_ValueError, "DST too long");
        return nullptr;
    }
    bls::G2 r;
    Py_BEGIN_ALLOW_THREADS
    r = bls::hash_to_g2(reinterpret_cast<const uint8_t*>(msg),
                        size_t(msg_len),
                        reinterpret_cast<const uint8_t*>(dst),
                        size_t(dst_len));
    Py_END_ALLOW_THREADS
    return g2_bytes(r);
}

PyObject* bls_g1_uncompress(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    if (len != 48) {
        PyErr_SetString(PyExc_ValueError, "bad G1 compressed length");
        return nullptr;
    }
    bls::G1 p;
    int rc;
    Py_BEGIN_ALLOW_THREADS
    rc = bls::g1_uncompress(reinterpret_cast<uint8_t*>(buf), &p);
    Py_END_ALLOW_THREADS
    if (rc < 0) {
        PyErr_SetString(PyExc_ValueError, "invalid compressed G1");
        return nullptr;
    }
    if (rc == 1) Py_RETURN_NONE;
    return g1_bytes(p);
}

PyObject* bls_g2_uncompress(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    if (len != 96) {
        PyErr_SetString(PyExc_ValueError, "bad G2 compressed length");
        return nullptr;
    }
    bls::G2 p;
    int rc;
    Py_BEGIN_ALLOW_THREADS
    rc = bls::g2_uncompress(reinterpret_cast<uint8_t*>(buf), &p);
    Py_END_ALLOW_THREADS
    if (rc < 0) {
        PyErr_SetString(PyExc_ValueError, "invalid compressed G2");
        return nullptr;
    }
    if (rc == 1) Py_RETURN_NONE;
    return g2_bytes(p);
}

PyObject* bls_g1_mul(PyObject*, PyObject* args) {
    PyObject* pt_obj;
    const char* k;
    Py_ssize_t klen;
    if (!PyArg_ParseTuple(args, "Oy#", &pt_obj, &k, &klen))
        return nullptr;
    bls::G1 p;
    if (!parse_g1(pt_obj, &p)) return nullptr;
    bls::G1 r;
    Py_BEGIN_ALLOW_THREADS
    r = p.inf ? p : bls::G1_mul_be_fast(
        p, reinterpret_cast<const uint8_t*>(k), size_t(klen));
    Py_END_ALLOW_THREADS
    return g1_bytes(r);
}

PyObject* bls_g2_mul(PyObject*, PyObject* args) {
    PyObject* pt_obj;
    const char* k;
    Py_ssize_t klen;
    if (!PyArg_ParseTuple(args, "Oy#", &pt_obj, &k, &klen))
        return nullptr;
    bls::G2 p;
    if (!parse_g2(pt_obj, &p)) return nullptr;
    bls::G2 r;
    Py_BEGIN_ALLOW_THREADS
    r = p.inf ? p : bls::G2_mul_be_fast(
        p, reinterpret_cast<const uint8_t*>(k), size_t(klen));
    Py_END_ALLOW_THREADS
    return g2_bytes(r);
}

// bls_g1_sum(blob) / bls_g2_sum(blob): sum of concatenated raw affine
// points (96B / 192B each; the python side filters infinities out of
// the blob).  Jacobian accumulation — one field inversion total
// instead of one per addition — is what makes the aggregate-pubkey
// assembly O(n) *cheap* adds: ~0.5 us/point vs ~50 us for the
// python affine loop (the only O(n) residue of aggregate-commit
// verification; docs/aggregate_commits.md).
PyObject* bls_g1_sum(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    if (len % 96 != 0) {
        PyErr_SetString(PyExc_ValueError, "blob not a multiple of 96");
        return nullptr;
    }
    const uint8_t* b = reinterpret_cast<uint8_t*>(buf);
    Py_ssize_t n = len / 96;
    bls::G1 out;
    bool coord_ok = true;
    Py_BEGIN_ALLOW_THREADS
    std::vector<bls::G1> pts(static_cast<size_t>(n));
    for (Py_ssize_t i = 0; i < n; i++) {
        pts[size_t(i)].inf = false;
        if (!bls::fp_from_be48(b + i * 96, &pts[size_t(i)].x) ||
            !bls::fp_from_be48(b + i * 96 + 48, &pts[size_t(i)].y)) {
            coord_ok = false;
            break;
        }
    }
    if (coord_ok) {
        std::vector<bls::Fp> sa(static_cast<size_t>(n) / 2 + 1);
        std::vector<bls::Fp> sb(static_cast<size_t>(n) / 2 + 1);
        out = bls::sum_affine<bls::G1, bls::Fp>(
            pts.data(), size_t(n), sa.data(), sb.data());
    }
    Py_END_ALLOW_THREADS
    if (!coord_ok) {
        PyErr_SetString(PyExc_ValueError, "G1 coordinate >= p");
        return nullptr;
    }
    return g1_bytes(out);
}

PyObject* bls_g2_sum(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    if (len % 192 != 0) {
        PyErr_SetString(PyExc_ValueError, "blob not a multiple of 192");
        return nullptr;
    }
    const uint8_t* b = reinterpret_cast<uint8_t*>(buf);
    Py_ssize_t n = len / 192;
    bls::G2 out;
    bool coord_ok = true;
    Py_BEGIN_ALLOW_THREADS
    std::vector<bls::G2> pts(static_cast<size_t>(n));
    for (Py_ssize_t i = 0; i < n; i++) {
        bls::G2& p = pts[size_t(i)];
        p.inf = false;
        if (!bls::fp_from_be48(b + i * 192, &p.x.c0) ||
            !bls::fp_from_be48(b + i * 192 + 48, &p.x.c1) ||
            !bls::fp_from_be48(b + i * 192 + 96, &p.y.c0) ||
            !bls::fp_from_be48(b + i * 192 + 144, &p.y.c1)) {
            coord_ok = false;
            break;
        }
    }
    if (coord_ok) {
        std::vector<bls::Fp2> sa(static_cast<size_t>(n) / 2 + 1);
        std::vector<bls::Fp2> sb(static_cast<size_t>(n) / 2 + 1);
        out = bls::sum_affine<bls::G2, bls::Fp2>(
            pts.data(), size_t(n), sa.data(), sb.data());
    }
    Py_END_ALLOW_THREADS
    if (!coord_ok) {
        PyErr_SetString(PyExc_ValueError, "G2 coordinate >= p");
        return nullptr;
    }
    return g2_bytes(out);
}

PyObject* sha256_one(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    uint8_t out[32];
    sha256::hash(reinterpret_cast<const uint8_t*>(buf), size_t(len),
                 out);
    return PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(out), 32);
}

// ed25519_batch_verify(items, z) -> int
// items: sequence of (pub, msg, sig) byte tuples; z: 16*len(items)
// random bytes (one 128-bit randomizer per item, bit 0 forced odd in
// C).  Returns 1 iff the RLC batch equation holds for every item
// (ZIP-215 semantics); 0 on any malformed input or batch reject —
// the caller falls back to the per-signature path for the mask.
// The CPU analog of the reference's voi batch verifier
// (crypto/ed25519/ed25519.go:189-222); see ed25519_msm.hpp.
PyObject* ed25519_batch_verify(PyObject*, PyObject* args) {
    PyObject* seq_in;
    const char* z_bytes;
    Py_ssize_t z_len;
    if (!PyArg_ParseTuple(args, "Oy#", &seq_in, &z_bytes, &z_len))
        return nullptr;
    PyObject* fast = PySequence_Fast(seq_in, "expected a sequence");
    if (!fast) return nullptr;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    if (z_len != n * 16) {
        Py_DECREF(fast);
        PyErr_SetString(PyExc_ValueError,
                        "need 16 randomizer bytes per item");
        return nullptr;
    }
    std::vector<ed25519_msm::BatchItem> items;
    items.reserve(size_t(n));
    std::vector<PyObject*> fits;
    fits.reserve(size_t(n));
    bool shape_ok = true;
    for (Py_ssize_t i = 0; i < n && shape_ok; i++) {
        PyObject* it = PySequence_Fast_GET_ITEM(fast, i);
        PyObject* fit = PySequence_Fast(it, "item must be a tuple");
        if (!fit || PySequence_Fast_GET_SIZE(fit) != 3) {
            PyErr_Clear();
            Py_XDECREF(fit);
            shape_ok = false;
            break;
        }
        fits.push_back(fit);
        char *pub, *msg, *sig;
        Py_ssize_t publen, msglen, siglen;
        if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 0),
                                    &pub, &publen) < 0 ||
            PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 1),
                                    &msg, &msglen) < 0 ||
            PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fit, 2),
                                    &sig, &siglen) < 0) {
            PyErr_Clear();
            shape_ok = false;
            break;
        }
        if (publen != 32 || siglen != 64) {
            shape_ok = false;
            break;
        }
        items.push_back(ed25519_msm::BatchItem{
            reinterpret_cast<uint8_t*>(pub),
            reinterpret_cast<uint8_t*>(msg), size_t(msglen),
            reinterpret_cast<uint8_t*>(sig)});
    }
    int ok = 0;
    if (shape_ok) {
        const uint8_t* z = reinterpret_cast<const uint8_t*>(z_bytes);
        int nt = 0;
        const char* env = getenv("COMETBFT_TPU_MSM_THREADS");
        if (env && *env) nt = atoi(env);
        if (nt <= 0) nt = ed25519_msm::default_threads();
        if (nt > 16) nt = 16;       // fan_out's clamp; part[] sizing
        Py_BEGIN_ALLOW_THREADS
        ok = ed25519_msm::batch_verify(items, z, nt);
        Py_END_ALLOW_THREADS
    }
    for (PyObject* fit : fits) Py_DECREF(fit);
    Py_DECREF(fast);
    return PyLong_FromLong(ok);
}

// ed25519_batch_verify_tile(pubs, msgs, lens, sigs, z) -> int
// The pipeline's per-tile entry (KERNEL_NOTES round 6): packed-blob
// calling convention — pubs 32n, sigs 64n, z 16n, msgs concatenated
// with lens as n little-endian uint32 — so a tile dispatch costs four
// buffer borrows instead of 3n PyObject extractions.  Returns 1 iff
// the tile's RLC batch equation holds (ZIP-215), 0 on malformed
// input or batch reject (caller bisects within the tile).  The
// signed-digit MSM + cached fe_sqr decompression run with the GIL
// released on the pipeline's kernel worker thread.
PyObject* ed25519_batch_verify_tile(PyObject*, PyObject* args) {
    const char *pubs, *msgs, *lens, *sigs, *z_bytes;
    const char* staged = nullptr;
    Py_ssize_t pubs_len, msgs_len, lens_len, sigs_len, z_len;
    Py_ssize_t staged_len = 0;
    if (!PyArg_ParseTuple(args, "y#y#y#y#y#|y#", &pubs, &pubs_len,
                          &msgs, &msgs_len, &lens, &lens_len,
                          &sigs, &sigs_len, &z_bytes, &z_len,
                          &staged, &staged_len))
        return nullptr;
    if (lens_len % 4 != 0) {
        PyErr_SetString(PyExc_ValueError,
                        "lens must be 4 bytes per item");
        return nullptr;
    }
    Py_ssize_t n = lens_len / 4;
    if (pubs_len != n * 32 || sigs_len != n * 64 || z_len != n * 16) {
        PyErr_SetString(PyExc_ValueError,
                        "need 32 pub / 64 sig / 16 z bytes per item");
        return nullptr;
    }
    if (staged != nullptr && staged_len !=
            n * Py_ssize_t(ed25519_msm::STAGED_REC)) {
        // a mismatched staged blob is ignored, not an error: it is a
        // pure speed memo and the verify pass decompresses itself
        staged = nullptr;
    }
    std::vector<ed25519_msm::TileView> items;
    items.reserve(size_t(n));
    const uint8_t* lp = reinterpret_cast<const uint8_t*>(lens);
    size_t off = 0;
    bool shape_ok = true;
    for (Py_ssize_t i = 0; i < n; i++) {
        uint32_t ml;
        std::memcpy(&ml, lp + i * 4, 4);
        if (off + ml > size_t(msgs_len)) {
            shape_ok = false;
            break;
        }
        items.push_back(ed25519_msm::TileView{
            reinterpret_cast<const uint8_t*>(pubs) + i * 32,
            reinterpret_cast<const uint8_t*>(msgs) + off, size_t(ml),
            reinterpret_cast<const uint8_t*>(sigs) + i * 64});
        off += ml;
    }
    if (!shape_ok || off != size_t(msgs_len)) {
        PyErr_SetString(PyExc_ValueError,
                        "msgs blob does not match lens");
        return nullptr;
    }
    int ok = 0;
    const uint8_t* z = reinterpret_cast<const uint8_t*>(z_bytes);
    Py_BEGIN_ALLOW_THREADS
    ok = ed25519_msm::batch_verify_tile(
        items, z, reinterpret_cast<const uint8_t*>(staged));
    Py_END_ALLOW_THREADS
    return PyLong_FromLong(ok);
}

// ed25519_stage_pubs(pubs_blob) -> staged points blob
// Resolve a blob of 32-byte pubkeys to decompressed A points,
// GIL-free — the pipeline's staging phase runs this for tile i+1
// while tile i's MSM executes on the kernel worker.  Cache hits copy
// out; misses decompress once and fill the shared cache.  The
// returned blob (81 bytes per key: raw affine x || y limbs +
// validity byte, process-internal representation) feeds the same
// tile's ed25519_batch_verify_tile call.
PyObject* ed25519_stage_pubs(PyObject*, PyObject* arg) {
    char* buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(arg, &buf, &len) < 0) return nullptr;
    if (len % 32 != 0) {
        PyErr_SetString(PyExc_ValueError,
                        "blob not a multiple of 32");
        return nullptr;
    }
    Py_ssize_t n = len / 32;
    PyObject* out = PyBytes_FromStringAndSize(
        nullptr, n * Py_ssize_t(ed25519_msm::STAGED_REC));
    if (!out) return nullptr;
    uint8_t* op = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    Py_BEGIN_ALLOW_THREADS
    ed25519_msm::stage_pubs(reinterpret_cast<const uint8_t*>(buf),
                            size_t(n), op);
    Py_END_ALLOW_THREADS
    return out;
}

// chacha20poly1305_seal(key, nonce, aad, plaintext) -> ct||tag
// The p2p secret-connection frame hot path when the python
// `cryptography` package is absent (see crypto/_aead_fallback.py).
PyObject* chacha20poly1305_seal(PyObject*, PyObject* args) {
    const char *key, *nonce, *aad, *pt;
    Py_ssize_t keyl, noncel, aadl, ptl;
    if (!PyArg_ParseTuple(args, "y#y#y#y#", &key, &keyl, &nonce,
                          &noncel, &aad, &aadl, &pt, &ptl))
        return nullptr;
    if (keyl != 32 || noncel != 12) {
        PyErr_SetString(PyExc_ValueError,
                        "key must be 32 bytes, nonce 12");
        return nullptr;
    }
    PyObject* out = PyBytes_FromStringAndSize(nullptr, ptl + 16);
    if (!out) return nullptr;
    ccp::seal(reinterpret_cast<const uint8_t*>(key),
              reinterpret_cast<const uint8_t*>(nonce),
              reinterpret_cast<const uint8_t*>(aad), size_t(aadl),
              reinterpret_cast<const uint8_t*>(pt), size_t(ptl),
              reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out)));
    return out;
}

// chacha20poly1305_open(key, nonce, aad, ct_and_tag) -> plaintext
// or None on tag mismatch.
PyObject* chacha20poly1305_open(PyObject*, PyObject* args) {
    const char *key, *nonce, *aad, *ct;
    Py_ssize_t keyl, noncel, aadl, ctl;
    if (!PyArg_ParseTuple(args, "y#y#y#y#", &key, &keyl, &nonce,
                          &noncel, &aad, &aadl, &ct, &ctl))
        return nullptr;
    if (keyl != 32 || noncel != 12 || ctl < 16) {
        PyErr_SetString(PyExc_ValueError,
                        "key must be 32 bytes, nonce 12, ct >= 16");
        return nullptr;
    }
    PyObject* out = PyBytes_FromStringAndSize(nullptr, ctl - 16);
    if (!out) return nullptr;
    bool ok = ccp::open(
        reinterpret_cast<const uint8_t*>(key),
        reinterpret_cast<const uint8_t*>(nonce),
        reinterpret_cast<const uint8_t*>(aad), size_t(aadl),
        reinterpret_cast<const uint8_t*>(ct), size_t(ctl),
        reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out)));
    if (!ok) {
        Py_DECREF(out);
        Py_RETURN_NONE;
    }
    return out;
}

PyMethodDef kMethods[] = {
    {"chacha20poly1305_seal", chacha20poly1305_seal, METH_VARARGS,
     "RFC 8439 AEAD seal: (key, nonce, aad, pt) -> ct||tag"},
    {"chacha20poly1305_open", chacha20poly1305_open, METH_VARARGS,
     "RFC 8439 AEAD open: (key, nonce, aad, ct||tag) -> pt | None"},
    {"merkle_root", merkle_root, METH_O,
     "RFC-6962/CometBFT merkle root of a sequence of bytes"},
    {"leaf_hashes", leaf_hashes, METH_O,
     "concatenated 32-byte leaf hashes"},
    {"sha256_many", sha256_many, METH_O,
     "concatenated SHA-256 digests of a sequence of bytes"},
    {"sha512_many", sha512_many, METH_O,
     "concatenated SHA-512 digests of a sequence of bytes"},
    {"ed25519_kscalars", ed25519_kscalars, METH_O,
     "concatenated SHA-512(item) mod L scalars (32B LE each)"},
    {"ed25519_batch_verify", ed25519_batch_verify, METH_VARARGS,
     "RLC batch verification of (pub, msg, sig) items (ZIP-215)"},
    {"ed25519_prep", ed25519_prep, METH_VARARGS,
     "full batch-verify host prep: (items, m, B, identity) -> "
     "(wire [m*192], pre_bad [m])"},
    {"ed25519_prep_begin", ed25519_prep_begin, METH_VARARGS,
     "the same prep as a future: phase 2 on the native thread; "
     "-> PrepHandle, whose result() is (wire, pre_bad, start_ns, "
     "elapsed_ns, waited_ns)"},
    {"ed25519_batch_verify_tile", ed25519_batch_verify_tile,
     METH_VARARGS,
     "per-tile RLC batch verification over packed blobs "
     "(pubs, msgs, lens, sigs, z[, staged]) -> 1/0"},
    {"ed25519_stage_pubs", ed25519_stage_pubs, METH_O,
     "resolve a 32n pubkey blob to a staged A-point blob "
     "(cache-backed decompression)"},
    {"bls_pairings_product_is_one", bls_pairings_product_is_one,
     METH_O, "prod e(P_i, Q_i) == 1 over raw affine pairs"},
    {"bls_selftest", bls_selftest, METH_NOARGS,
     "Frobenius + fast-final-exponentiation consistency check"},
    {"bls_g1_in_subgroup", bls_g1_in_subgroup, METH_O,
     "curve + r-order check for a raw affine G1 point"},
    {"bls_g2_in_subgroup", bls_g2_in_subgroup, METH_O,
     "curve + r-order check for a raw affine G2 point"},
    {"bls_hash_to_g2", bls_hash_to_g2, METH_VARARGS,
     "hash_to_g2(msg, dst) -> raw affine G2"},
    {"bls_g1_uncompress", bls_g1_uncompress, METH_O,
     "ZCash-flag compressed 48B -> raw affine G1 | None (infinity)"},
    {"bls_g2_uncompress", bls_g2_uncompress, METH_O,
     "ZCash-flag compressed 96B -> raw affine G2 | None (infinity)"},
    {"bls_g1_sum", bls_g1_sum, METH_O,
     "sum of concatenated raw affine G1 points"},
    {"bls_g2_sum", bls_g2_sum, METH_O,
     "sum of concatenated raw affine G2 points"},
    {"bls_g1_mul", bls_g1_mul, METH_VARARGS,
     "scalar multiple of a raw affine G1 point (k big-endian)"},
    {"bls_g2_mul", bls_g2_mul, METH_VARARGS,
     "scalar multiple of a raw affine G2 point (k big-endian)"},
    {"sha256", sha256_one, METH_O, "SHA-256 of one bytes object"},
    {"wire_encode", _PyCFunction_CAST(wire::wire_encode), METH_FASTCALL,
     "wire/proto.py encode(desc, dict) -> bytes | None (declined)"},
    {"wire_decode", _PyCFunction_CAST(wire::wire_decode), METH_FASTCALL,
     "wire/proto.py decode(desc, bytes) -> dict | None (declined)"},
    {"wire_stats", wire::wire_stats, METH_NOARGS,
     "(calls the wire executor answered, calls it declined)"},
    {"commit_walk", commit_walk::walk, METH_VARARGS,
     "types/validation's batched by-index walk, a tile at a time: "
     "(sigs, start, keys, powers, templates, ignore, count, needed, "
     "tallied, count_all, triples, tile) -> (status, next, tallied, "
     "lanes)"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef kModule = {
    PyModuleDef_HEAD_INIT, "_native",
    "C++ fast paths: merkle tree + batch SHA-256", -1, kMethods,
    nullptr, nullptr, nullptr, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit__native(void) {
    if (!prep_handle_type_ready()) return nullptr;
    return PyModule_Create(&kModule);
}
