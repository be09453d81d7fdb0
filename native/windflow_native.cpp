// Native host-runtime core for windflow_tpu.
//
// Plays the role FastFlow plays for the reference (SURVEY.md L0):
// bounded channels with per-producer EOS accounting carrying opaque
// item handles (PyObject* from the Python plane, any pointer from a
// future all-native plane), plus the vectorizable host-plane kernels of
// the columnar dataplane (key partitioning, pane partial reduction).
//
// Exposed as a plain C ABI consumed via ctypes
// (windflow_tpu/runtime/native.py) -- no pybind11 dependency.
//
// Threading contract: all blocking waits happen outside the Python GIL
// (ctypes releases it around foreign calls), so a Python producer
// blocked on a full channel never stalls consumers.

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <vector>

namespace {

struct Item {
    int producer;
    std::uintptr_t handle;
    bool eos;
};

// Bounded MPSC channel with per-producer EOS accounting
// (the FF_BOUNDED_BUFFER-backpressure analogue).
struct Channel {
    explicit Channel(std::size_t cap) : capacity(cap) {}

    std::size_t capacity;
    std::mutex mu;
    std::condition_variable not_full;
    std::condition_variable not_empty;
    std::deque<Item> q;
    int n_producers = 0;
    int eos_seen = 0;
    bool poisoned = false;  // graph-cancellation shutdown sentinel

    int register_producer() {
        std::lock_guard<std::mutex> lk(mu);
        return n_producers++;
    }

    // 1 = accepted, -1 = channel poisoned (item not enqueued; the
    // caller still owns the handle's reference).
    int put(int producer, std::uintptr_t handle, bool eos) {
        std::unique_lock<std::mutex> lk(mu);
        not_full.wait(lk, [&] {
            return q.size() < capacity || eos || poisoned;
        });
        if (poisoned) return -1;
        q.push_back(Item{producer, handle, eos});
        not_empty.notify_one();
        return 1;
    }

    void poison() {
        std::lock_guard<std::mutex> lk(mu);
        poisoned = true;
        not_full.notify_all();
        not_empty.notify_all();
    }

    // One popped item through the EOS protocol (lock held, q nonempty):
    // 1 = delivered, 0 = all producers closed, -1 = swallowed EOS.
    int pop_locked(std::uintptr_t* handle, int* cid) {
        Item it = q.front();
        q.pop_front();
        not_full.notify_one();
        if (it.eos) {
            if (++eos_seen >= n_producers) return 0;
            return -1;
        }
        *handle = it.handle;
        *cid = it.producer;
        return 1;
    }

    // Returns 1 with *handle/*cid set; 0 once every producer closed;
    // -2 when poisoned (any undelivered items are drained at free time).
    int get(std::uintptr_t* handle, int* cid) {
        std::unique_lock<std::mutex> lk(mu);
        for (;;) {
            not_empty.wait(lk, [&] { return !q.empty() || poisoned; });
            if (poisoned) return -2;
            int rc = pop_locked(handle, cid);
            if (rc >= 0) return rc;
        }
    }

    // Timed variant for idle-tick consumers: additionally returns 2
    // when the timeout elapses with nothing to deliver.
    int get_timed(std::uintptr_t* handle, int* cid, long long timeout_ms) {
        std::unique_lock<std::mutex> lk(mu);
        auto deadline = std::chrono::steady_clock::now()
            + std::chrono::milliseconds(timeout_ms);
        for (;;) {
            if (!not_empty.wait_until(lk, deadline,
                                      [&] { return !q.empty() || poisoned; }))
                return 2;
            if (poisoned) return -2;
            int rc = pop_locked(handle, cid);
            if (rc >= 0) return rc;
        }
    }

    // Post-poison drain for the owner thread: returns remaining item
    // handles one by one so the binding can release their references.
    int drain(std::uintptr_t* handle) {
        std::lock_guard<std::mutex> lk(mu);
        while (!q.empty()) {
            Item it = q.front();
            q.pop_front();
            if (it.eos) continue;
            *handle = it.handle;
            return 1;
        }
        return 0;
    }

    std::size_t size() {
        std::lock_guard<std::mutex> lk(mu);
        return q.size();
    }
};

}  // namespace

extern "C" {

void* wfn_channel_new(std::size_t capacity) {
    return new Channel(capacity == 0 ? 1 : capacity);
}

void wfn_channel_free(void* ch) { delete static_cast<Channel*>(ch); }

int wfn_channel_register_producer(void* ch) {
    return static_cast<Channel*>(ch)->register_producer();
}

int wfn_channel_put(void* ch, int producer, std::uintptr_t handle) {
    return static_cast<Channel*>(ch)->put(producer, handle, false);
}

void wfn_channel_close(void* ch, int producer) {
    static_cast<Channel*>(ch)->put(producer, 0, true);
}

void wfn_channel_poison(void* ch) {
    static_cast<Channel*>(ch)->poison();
}

int wfn_channel_drain(void* ch, std::uintptr_t* handle) {
    return static_cast<Channel*>(ch)->drain(handle);
}

int wfn_channel_get(void* ch, std::uintptr_t* handle, int* cid) {
    return static_cast<Channel*>(ch)->get(handle, cid);
}

int wfn_channel_get_timed(void* ch, std::uintptr_t* handle, int* cid,
                          long long timeout_ms) {
    return static_cast<Channel*>(ch)->get_timed(handle, cid, timeout_ms);
}

std::size_t wfn_channel_size(void* ch) {
    return static_cast<Channel*>(ch)->size();
}

// --- columnar host kernels -------------------------------------------------

// Pane partial sums: out[i] = sum(values[pos[i] .. pos[i+1]))
// (the host PLQ pre-reduction of the transport optimization).
void wfn_pane_sum(const double* values, const long long* pos,
                  long long n_panes, double* out) {
    for (long long i = 0; i < n_panes; ++i) {
        double acc = 0.0;
        for (long long j = pos[i]; j < pos[i + 1]; ++j) acc += values[j];
        out[i] = acc;
    }
}

void wfn_pane_max(const double* values, const long long* pos,
                  long long n_panes, double neutral, double* out) {
    for (long long i = 0; i < n_panes; ++i) {
        double acc = neutral;
        for (long long j = pos[i]; j < pos[i + 1]; ++j)
            if (values[j] > acc) acc = values[j];
        out[i] = acc;
    }
}

void wfn_pane_min(const double* values, const long long* pos,
                  long long n_panes, double neutral, double* out) {
    for (long long i = 0; i < n_panes; ++i) {
        double acc = neutral;
        for (long long j = pos[i]; j < pos[i + 1]; ++j)
            if (values[j] < acc) acc = values[j];
        out[i] = acc;
    }
}

// KEYBY partitioning of a columnar batch: dest[i] = |keys[i]| % ndest,
// and per-destination counts (the vectorized Standard/KF emitter).
void wfn_partition_mod(const long long* keys, long long n, long long ndest,
                       int* dest, long long* counts) {
    std::memset(counts, 0, sizeof(long long) * ndest);
    for (long long i = 0; i < n; ++i) {
        long long k = keys[i];
        if (k < 0) k = -k;
        int d = static_cast<int>(k % ndest);
        dest[i] = d;
        ++counts[d];
    }
}

// Mask to rows: out[0..k) = the indices i with mask[i] != 0, ascending,
// and k returned (TupleBatch.take of a filter's mask, core/tuples.py:
// what np.nonzero answers, bit for bit).  Branch-free: every index is
// stored and the cursor moves by whether the byte was set, so a mask a
// third full costs what an empty one does (np.nonzero takes a
// mispredicted branch a row).  `out` holds n entries.  (Eight bytes at a
// time through a table of places read no faster on the chip's host, which
// has AVX2 and no AVX-512: 32.9 us against 30.5 for 65,536 rows, PR 31.)
long long wfn_mask_to_rows(const unsigned char* mask, long long n,
                           long long* out) {
    long long k = 0;
    for (long long i = 0; i < n; ++i) {
        out[k] = i;
        k += mask[i] != 0;
    }
    return k;
}

}  // extern "C"
