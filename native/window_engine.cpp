// Native columnar window engine: the C++ batch assembler of the device
// window path (SURVEY.md §7 step 4: "batch assembler (pinned host
// buffers -> PJRT device buffers)" belongs in the native runtime).
//
// Covers the hot standalone case of Win_Seq_TPU (role SEQ, identity
// WinOperatorConfig, int64 keys, builtin combines): ingest columnar
// batches, detect fired windows, and stage pane-partial flat buffers +
// extents for one XLA launch.  The Python engine
// (operators/tpu/win_seq_tpu.py) delegates here when the workload
// matches and falls back otherwise (roles, custom functors, string
// keys).
//
// The state model is the Pane decomposition (Li et al., SIGMOD 2005;
// reference wf/pane_farm.hpp:33-35) applied at INGEST time: because the
// engine only runs builtin associative combines, it never stores the
// tuple stream at all.  Each key holds a small ring of pane
// accumulators (pane = gcd(win, slide), so every window is an exact
// pane range) and a chunk's tuples are folded into their panes as it
// arrives -- by key: one table probe a tuple, one combine a key and
// chunk where the key's tuples lie in one pane (Engine, "THE FOLD IS BY
// KEY"), instead of the scatter-copy of the full value series that a
// CUDA staging design implies (win_seq_gpu.hpp:552-596 archives tuples
// per key and re-reads them per batch; on a TPU host that second pass
// is pure memory-bandwidth waste).  Late tuples within the retained
// pane range fold in exactly like the archive insert would; tuples
// behind the fired frontier are dropped, matching the scalar path's
// acceptance rule (win_seq.hpp:417-428).
//
// GIL-free: every entry point only touches caller-provided arrays and
// internal state; Python calls via ctypes release the GIL.

#include <pthread.h>
#include <sched.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <limits>
#include <map>
#include <mutex>
#include <new>
#include <numeric>
#include <system_error>
#include <thread>
#include <vector>

namespace {

using i64 = long long;

constexpr double INF = std::numeric_limits<double>::infinity();

inline i64 now_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
        std::chrono::steady_clock::now().time_since_epoch()).count();
}

// A FEW HOST LANES, ASLEEP BETWEEN CALLS.  What the engine hands to
// more than one core: the walk over a call's tuples, in slices (Engine,
// "THE TUPLE WALK IN SLICES").  One pool for the process, made by the
// first call that engages it -- not when the library loads, not when an
// engine is made -- so until then no thread exists.  A job is a few
// pieces, each taken by whichever lane comes first, the calling thread
// among them: a lane slow to wake costs the caller that lane's piece,
// never a wait for it.  A lane with nothing to take spins for SPIN_NS,
// then sleeps on a condition variable: a process whose calls stop (a
// paced feed between chunks, a burst's silence) pays no core for its
// lanes.  One call at a time (a try-lock: another engine's call
// meanwhile walks alone); a forked child never touches its parent's
// pool (the pid tells them apart) and makes its own; at exit the lanes
// are stopped and joined.
namespace lanes {

// The caller and up to MAX - 1 helpers.  At four the walk is a quarter
// of what it was and the rest of the calling thread (the generator, the
// flush, the Python about the calls: a quarter of it in the 111-key
// cell) paces it; a fifth to eighth lane would buy at most another
// tenth of the thread for a wake and a merge each (docs/RUNTIME.md 5b)
constexpr int MAX = 4;
// a lane's spin before it sleeps: what a merge between two pieces of a
// call takes, with room; a sleeping lane's wake is a futex call
constexpr i64 SPIN_NS = 20000;

inline void relax() {
#if defined(__x86_64__) || defined(__i386__)
    __builtin_ia32_pause();
#endif
}

struct Pool {
    using Fn = void (*)(void*, int);
    const pid_t pid;
    int lanes = 1;                    // the caller and the helpers
    std::vector<std::thread> helpers;
    std::vector<clockid_t> clocks;    // each helper's CPU clock
    // the job: written by the caller before it publishes `claim`, read
    // by a lane once it has taken a piece (it cannot change before that
    // piece is done)
    Fn fn = nullptr;
    void* ctx = nullptr;
    // the job's generation (bits 16 on), its pieces (8..15) and the
    // next piece to take (0..7): one word, so a lane can only take a
    // piece of the job it has read
    std::atomic<std::uint64_t> claim{0};
    std::atomic<int> done{0};
    std::atomic<int> sleepers{0};
    std::atomic<bool> stop{false};
    std::mutex m;
    std::condition_variable cv;

    Pool(pid_t p, int n) : pid(p) {
        try {
            for (int i = 1; i < n; ++i) {
                helpers.emplace_back([this] { serve(); });
                clockid_t c;
                if (pthread_getcpuclockid(helpers.back().native_handle(),
                                          &c) == 0)
                    clocks.push_back(c);
            }
        } catch (const std::system_error&) {
            // fewer threads than asked for: the caller takes their pieces
        }
        lanes = 1 + (int)helpers.size();
    }

    static bool open(std::uint64_t c) {
        return (c & 0xff) < ((c >> 8) & 0xff);
    }

    // a piece of the job, or -1 where none is left to take
    int take() {
        std::uint64_t c = claim.load(std::memory_order_acquire);
        while (open(c))
            if (claim.compare_exchange_weak(c, c + 1,
                                            std::memory_order_acq_rel,
                                            std::memory_order_acquire))
                return (int)(c & 0xff);
        return -1;
    }

    void work() {
        for (int k; (k = take()) >= 0;) {
            fn(ctx, k);
            done.fetch_add(1, std::memory_order_release);
        }
    }

    // a helper: pieces while there are any, a spin, then sleep
    void serve() {
        while (true) {
            work();
            const i64 t0 = now_ns();
            for (int spin = 1; !open(claim.load(std::memory_order_acquire));
                 ++spin) {
                if (stop.load(std::memory_order_relaxed)) return;
                relax();
                if ((spin & 63) == 0 && now_ns() - t0 > SPIN_NS) {
                    // counted before the job is read again, and the
                    // caller reads the count after publishing: one of
                    // the two sees the other (sequentially consistent)
                    std::unique_lock<std::mutex> lk(m);
                    sleepers.fetch_add(1);
                    cv.wait(lk, [this] { return open(claim.load())
                                                || stop.load(); });
                    sleepers.fetch_sub(1);
                    break;
                }
            }
            if (stop.load()) return;
        }
    }

    // fn(ctx, k) for k in [0, n), the caller taking pieces too
    void run(Fn f, void* c, int n) {
        fn = f;
        ctx = c;
        done.store(0, std::memory_order_relaxed);
        const std::uint64_t gen =
            (claim.load(std::memory_order_relaxed) >> 16) + 1;
        claim.store(gen << 16 | (std::uint64_t)n << 8);
        if (sleepers.load() > 0) {
            { std::lock_guard<std::mutex> lk(m); }
            cv.notify_all();
        }
        work();
        for (int spin = 1; done.load(std::memory_order_acquire) < n; ++spin) {
            relax();
            if ((spin & 1023) == 0) sched_yield();
        }
    }

    void halt() {
        stop.store(true);
        { std::lock_guard<std::mutex> lk(m); }
        cv.notify_all();
        for (std::thread& t : helpers)
            if (t.joinable()) t.join();
    }

    // the helpers' CPU time, nanoseconds
    i64 cpu_ns() const {
        i64 sum = 0;
        for (clockid_t c : clocks) {
            timespec ts;
            if (clock_gettime(c, &ts) == 0)
                sum += (i64)ts.tv_sec * 1000000000 + ts.tv_nsec;
        }
        return sum;
    }
};

// never freed: a helper of a call still running as the process exits
// finds it where it was
std::atomic<Pool*> the_pool{nullptr};
std::atomic_flag busy = ATOMIC_FLAG_INIT;
std::atomic<long long> here_of{0};    // pid << 8 | lanes

// The lanes this process may have: where its CPU affinity leaves two
// cores or more beyond the caller, the cores but one (the process's
// other threads keep it: the dispatcher, the device runtime's), at most
// MAX; else 1, none.  Read once a process (a forked child reads again).
inline int here() {
    const pid_t me = getpid();
    const long long c = here_of.load(std::memory_order_relaxed);
    if ((c >> 8) == me) return (int)(c & 0xff);
    cpu_set_t set;
    int cores = 1;
    if (sched_getaffinity(0, sizeof set, &set) == 0) cores = CPU_COUNT(&set);
    const int l = cores >= 3 ? std::min(MAX, cores - 1) : 1;
    here_of.store((long long)me << 8 | l, std::memory_order_relaxed);
    return l;
}

// A call's hold on the lanes: `held` where no other call has them, and
// then the pool of this process (made here the first time; null where
// the process may have no helper)
struct Hold {
    Pool* pool = nullptr;
    const bool held;
    Hold() : held(!busy.test_and_set(std::memory_order_acquire)) {
        if (!held) return;
        Pool* p = the_pool.load(std::memory_order_acquire);
        const pid_t me = getpid();
        if (p == nullptr || p->pid != me) {
            // none yet, or a parent's: a forked child leaves it as it is
            const int l = here();
            p = l > 1 ? new Pool(me, l) : nullptr;
            if (p != nullptr) the_pool.store(p, std::memory_order_release);
        }
        pool = p;
    }
    ~Hold() {
        if (held) busy.clear(std::memory_order_release);
    }
    Hold(const Hold&) = delete;
    Hold& operator=(const Hold&) = delete;

    // fn(ctx, k) for k in [0, n): on the lanes, else on the caller
    void run(Pool::Fn fn, void* ctx, int n) const {
        if (pool != nullptr) {
            pool->run(fn, ctx, n);
            return;
        }
        for (int k = 0; k < n; ++k) fn(ctx, k);
    }
};

struct Reaper {
    ~Reaper() {
        Pool* p = the_pool.load();
        if (p != nullptr && p->pid == getpid()) p->halt();
    }
} reaper;

}  // namespace lanes

// A KEY STATE IN ONE PLACE.  What a key's first tuple of a call walks:
//
//   tab[home_of(key)]  ->  KeyState (pool block base + slot * 192 B)
//   24 B record             line 0  the hot line: what prepare(),
//                                   fold_key(), settle(), fire_key(),
//                                   flush() and evict() read
//                           line 1  the ring's first RING_IN panes
//                                   (acc, cnt)   |  or, once the ring has
//                                   left the key state, {block, cap}  ->
//                                   one heap block: acc[cap] cnt[cap]
//                                   (CB lanes: lid[cap] lts[cap] after)
//                           line 2  due_at, anchor, arrivals, a spare block
//
// The slot's address is arithmetic, the ring of a key whose windows are
// a few panes (every TB lane whose call spans few panes of a key) lies
// directly behind the line that addresses it, and nothing else is
// allocated a key.  A ring leaves the key state ("spills") where it
// outgrows RING_IN panes: long windows over a small pane, a call that
// spans many panes of one key, move_back() growing it at the front, and
// every ring of a CB lane (its two extra lanes, the last tuple's id and
// stamp a pane, have no room inline).  RING_IN is what one line holds.
constexpr int RING_IN = 4;    // 64 B / (an accumulator + a count)

struct alignas(64) KeyState {
    // -- line 0 --
    i64 key = 0;              // here and not behind: evict() finds the
                              // table record by it, straight after
                              // flush() has had this line and no other
                              // (46 us a chunk of 4,300 dying keys, PR 33)
    i64 pane_base = 0;        // absolute pane index of the ring's pane 0
    i64 next_fire = 0;        // next window (lwid) to fire
    i64 opened_max = -1;
    i64 max_id = -1;
    i64 staged_upto = -1;     // last window flush() staged for the key
    int32_t queued = 0;       // fired windows of the key waiting in `ready`
    // The pane-partial ring: pane j is absolute pane (pane_base + j), its
    // combine partial and its tuple count; on CB lanes also the max
    // tuple id seen in it and that tuple's stamp -- the CB
    // result-timestamp lane (result ts = ts of the last tuple in the
    // window extent, matching the host engine); TB windows' result ts is
    // pure window arithmetic.  `len` panes long as a snapshot states it
    // (ensure_pane()'s headroom included); the first `room` of them are
    // stored, the others hold nothing yet.
    int32_t len = 0;
    int32_t room = 0;         // min(len, the storage's capacity)
    bool live = false;        // the pool slot holds a key
    bool indexed = false;     // listed in `due` under due_at
    bool spilled = false;     // the ring lives in `out.block`
    // -- line 1 --
    union {
        struct { double acc[RING_IN]; i64 cnt[RING_IN]; } in;
        struct { char* block; i64 cap; } out;
    };
    // -- line 2 --
    i64 due_at = -1;          // the window the key's listing that counts
                              // is under (an earlier listing, left behind
                              // when the anchor moved back, is passed over):
                              // trigger()'s, which reads all three lines
    i64 anchor = 0;           // first window that can ever fire for this
                              // key (set from the first tuple; windows
                              // before it are never emitted, matching
                              // the on-demand window creation of the
                              // scalar path, win_seq.hpp:417-428)
    i64 arrivals = 0;         // renumber lane: running arrival count
                              // (ids implicit; such keys are never evicted)
    char* spare = nullptr;    // the block a former key of the slot left:
    i64 spare_cap = 0;        // a reused slot allocates nothing

    KeyState() : out{nullptr, 0} {}

    // back to a fresh slot; a block stays with the slot
    void reset() {
        if (spilled) {
            if (out.cap > spare_cap) {
                ::operator delete(spare);
                spare = out.block;
                spare_cap = out.cap;
            } else {
                ::operator delete(out.block);
            }
        }
        pane_base = next_fire = anchor = arrivals = 0;
        opened_max = max_id = staged_upto = due_at = -1;
        queued = len = room = 0;
        live = indexed = spilled = false;
    }
};
static_assert(sizeof(KeyState) == 192 && offsetof(KeyState, in) == 64
              && offsetof(KeyState, due_at) == 128, "KeyState's three lines");

// a ring's lanes, wherever it lives (Engine::ring)
struct Ring {
    double* acc;
    i64* cnt;
    i64* lid;                 // CB lanes only
    i64* lts;
    i64* aid = nullptr;       // ARGMAX only: the first id at the pane's max
};

struct Desc {
    i64 key, lwid;
    int32_t slot;
};

// ARGMAX: a pane keeps its largest value and the ids of the tuples at
// it (Engine, "THE IDS AT A PANE'S MAXIMUM"); a window's rows are those
// ids, each with the value
// TUPLES: a pane keeps its tuples, not a partial (Engine, "A PANE THAT
// KEEPS ITS TUPLES"); a window's rows are what a user's function makes
// of its raw values on the device
enum class Kind : int { SUM = 0, COUNT = 1, MAX = 2, MIN = 3, MEAN = 4,
                        ARGMAX = 5, TUPLES = 6 };

struct Engine {
    i64 win, slide, delay;
    bool is_tb;
    bool renumber;            // ids are implicit per-key arrival order
                              // (TS_RENUMBERING analogue): the id input
                              // is ignored
    // THE FIRING RULE.  Window w of a key fires once the frontier has
    // reached w*slide + win + delay and the key has opened w.  For TB
    // windows on real stamps (`stream_rule`) the frontier is the
    // engine's stream time, the largest stamp ingested over all keys:
    // a key that goes quiet gets its rows when the stream passes them,
    // not at EOS, and the input must be ordered per engine up to
    // `delay` (docs/RUNTIME.md "When a window fires").  For CB windows
    // and renumbered ids, which count a key's own arrivals, it is the
    // key's own largest id.
    bool stream_rule;
    // `stream_rule` engines that are not `dense` emit a row only for a
    // window that holds a tuple of the key and evict a key once its
    // last opened window has been staged; a key that comes back is a
    // new key.  `dense` engines (output ids are per-key counters, or a
    // lowered record graph whose host twin emits them) emit every
    // window from the key's anchor on and keep every key.
    bool dense, sparse;
    // the lanes whose pane state one combine a key can carry (see "THE
    // FOLD IS BY KEY" below)
    bool by_key_lane;
    Kind kind;
    i64 pane;                 // gcd(win, slide)
    i64 ppw;                  // panes per window
    int pshift;               // log2(pane) when pane is a power of two
    double neutral;
    // key states live in a pool whose slots are reused; the table
    // below maps key -> slot.  The pool is blocks of POOL_BLOCK states
    // that never move (d_state holds addresses for the length of a
    // call, `takes` and `due` hold slots): a slot's address is its
    // block's base and a multiple of the state's size, with the few
    // bases in one small array
    static constexpr int POOL_SHIFT = 12;
    static constexpr int32_t POOL_BLOCK = 1 << POOL_SHIFT;
    std::vector<KeyState*> blocks;
    int32_t n_slots = 0;
    std::vector<int32_t> free_slots;
    inline KeyState& state(int32_t slot) {
        return blocks[slot >> POOL_SHIFT][slot & (POOL_BLOCK - 1)];
    }
    inline const KeyState& state(int32_t slot) const {
        return blocks[slot >> POOL_SHIFT][slot & (POOL_BLOCK - 1)];
    }
    i64 n_live = 0;
    std::vector<Desc> ready;  // fired, unstaged; consumed from ready_head
    std::size_t ready_head = 0;
    i64 ignored = 0;          // tuples that belonged to a window and were
                              // not folded: behind a window that had fired
                              // (the stream's last, or a CB key's own), or
                              // below the anchor of a key that cannot move
                              // it (CB windows, renumbered ids)
    // disorder, counted: tuples whose stamp lay behind the stream time
    // when they came (the largest stamp of every tuple before them) as
    // gather() met them, those of them that were then dropped, and the
    // times a live key's anchor moved back (move_back)
    i64 late_seen = 0, late_dropped = 0, anchors_moved = 0;
    // stream-time trigger: keys with an opened, unfired window are
    // listed under the window they fire next, so a firing visits the
    // keys that fire
    i64 stream_time = -1;
    i64 fired_upto = -1;      // highest window the stream has passed
    std::map<i64, std::vector<int32_t>> due;
    // what key churn costs, read by wfn_engine_stats (nanoseconds on a
    // steady clock, taken per call and only when there is such work)
    i64 open_ns = 0, trigger_ns = 0, evict_ns = 0;
    i64 keys_opened = 0, keys_evicted = 0, keys_live_peak = 0;
    i64 windows_fired = 0;
    // staging buffers (valid until the next flush)
    std::vector<double> st_vals, st_cnts;
    i64 st_n = 0;                     // panes staged by the last flush
    template <typename T>
    static void grow(std::vector<T>& v, i64 n) {
        if ((i64)v.size() < n) v.resize(n);
    }
    std::vector<i64> st_starts, st_ends, st_keys, st_gwids, st_rts;
    std::vector<i64> f_prefix;        // flush(): count prefixes, flat
    std::vector<int32_t> f_touched, f_dead;
    i64 flush_id = 0;
    // what the fold did with the tuples it accepted, since the engine
    // was made: folded with their key's other tuples of the call in one
    // combine, or one by one (wfn_engine_stats)
    i64 folded_by_key = 0, folded_singly = 0;
    // WHERE A CALL RUNS AHEAD OF ITSELF.  On a table that has outgrown
    // AHEAD_TABLE_BYTES (of the order of a core's second-level cache:
    // some thousand live keys) a key's first touch in a call is a trip
    // to memory, and the walks fetch what they will need before they
    // need it: gather() the table records of its next block of tuples,
    // dense_of() the key state at the key's first tuple, the visits by
    // slot (prepare / settle, trigger()'s due list) the state some keys
    // ahead (flush()'s visits and the eviction's probes were tried and
    // gave nothing: a firing's keys are the ones trigger() has just
    // visited).  On a smaller table everything a call touches is in
    // the caches and the walks run as they are written: THE TUPLE WALK
    // IS BOUND BY WHAT IT DOES A TUPLE (two micro-ops more a tuple cost
    // the 111-key cell 6-7 %, PR 32), so running ahead is a template
    // parameter of gather() like SEL, and what decides is the table's
    // size, which is in front of the code: no option.  Counted: the keys
    // prepare() visited over all calls, those of them in a call that
    // ran ahead, and the rings that left their key state (KeyState).
    static constexpr std::size_t AHEAD_TABLE_BYTES = 256u << 10;
    i64 key_touches = 0, walked_ahead = 0, rings_spilled = 0;
    // what flush() staged since the engine was made: the pane partials
    // it copied into launch buffers (a key's span once a take, however
    // many of the key's windows the take holds) and the windows they
    // serve.  2 panes a window where a window is two panes and a key
    // stages one a launch; 117 where a launch stages 31 windows of 3,600
    // panes over one span of 3,630 (wfn_engine_stats)
    i64 panes_staged = 0, windows_staged = 0;
    // gather()'s own fetches cost every tuple a few micro-ops, and pay
    // only where first touches land on records the walk has not just
    // been near: keys the call did not open (a new key's record lies
    // beside its predecessor's, home_of(), which the hardware follows).
    // So the probe runs ahead in a call that follows one with more than
    // one such touch a block of tuples: stragglers to quiet keys, keys
    // drawn at random from a large population; not a population that
    // only advances
    bool probes_ahead = false;
    // scatter-ingest machinery: an open-addressing table (linear
    // probing from home_of) of one record a key: its pool slot and,
    // under the stamp of the call that wrote it, its index into the
    // per-call arrays.  An entry leaves the table by a backward shift,
    // so it holds no tombstones and forgets as fast as it learns.
    //
    // THE FOLD IS BY KEY.  A call walks its tuples once: one probe a
    // tuple, and the key's partial of this call (how many, the smallest
    // and largest id, for MAX / MIN the extreme value) is brought up to
    // date.  Then each key is visited once: prepare() fixes its anchor,
    // acceptance boundary and ring room, and where the key's tuples all
    // lie in one pane at or above that boundary the partial goes into
    // the pane with one combine (fold_key).  Only the keys that fail
    // that test -- the call straddles a pane edge, holds a late or
    // hopping-gap tuple, or the lane keeps more than a combine can carry
    // (SUM / MEAN add in arrival order, CB and renumbered ids stamp a
    // pane's last tuple) -- have their tuples folded one by one in a
    // second walk, which does not run where no key needs it.
    struct Entry {
        i64 key;
        i64 stamp;            // call_id of the last ingest that met the key
        int32_t slot;         // -1: empty
        int32_t dense;
    };
    std::vector<Entry> tab;
    i64 call_id = 0;
    // per-call arrays (index = order of first touch this call)
    struct Part {
        i64 count;            // the key's tuples in this call
        i64 lo, hi;           // their smallest and largest id
        double ext;           // MAX / MIN: the extreme of their values
    };
    std::vector<Part> parts;
    std::vector<KeyState*> d_state;
    std::vector<i64> d_accept;
    std::vector<int32_t> d_slot;
    std::vector<unsigned char> d_single;  // the key's tuples fold one by one
    i64 n_single = 0;                     // such keys in this call
    std::vector<int32_t> opened_now;  // dense indices of keys opened this call
    std::vector<int32_t> slot_of;     // per-tuple dense index

    Engine(i64 w, i64 s, bool tb, i64 d, bool renum, Kind k, bool dns)
        : win(w), slide(s), delay(tb ? d : 0), is_tb(tb), renumber(renum),
          stream_rule(tb && !renum), dense(dns),
          sparse(tb && !renum && !dns),
          by_key_lane(tb && !renum && w >= s
                      && (k == Kind::COUNT || k == Kind::MAX
                          || k == Kind::MIN || k == Kind::ARGMAX)),
          kind(k), pane(std::gcd(w, s)) {
        ppw = win / pane;
        pshift = (pane & (pane - 1)) == 0 ? __builtin_ctzll(pane) : -1;
        neutral = kind == Kind::MAX || kind == Kind::ARGMAX ? -INF
                : kind == Kind::MIN ? INF : 0.0;
        clear_table(1024);
    }

    void clear_table(std::size_t m) {
        tab.assign(m, Entry{0, -1, -1, 0});
    }

    inline bool runs_ahead() const {
        return tab.size() * sizeof(Entry) > AHEAD_TABLE_BYTES;
    }

    static inline void fetch(const void* p) { __builtin_prefetch(p); }
    // a key state's hot line and its ring's
    static inline void fetch_state(const KeyState* st) {
        fetch(st);
        fetch(reinterpret_cast<const char*>(st) + 64);
    }
    // the panes round relative pane `p` of a ring that left its key
    // state (the state's own lines are at hand)
    inline void fetch_ring(const KeyState& st, i64 p) const {
        if (!st.spilled || st.room == 0) return;
        p = p < 0 ? 0 : p < st.room ? p : st.room - 1;
        const Ring r = ring(st);
        fetch(r.acc + p);
        fetch(r.cnt + p);
    }

    ~Engine() { drop_pool(); }
    Engine(const Engine&) = delete;
    Engine& operator=(const Engine&) = delete;

    void drop_pool() {
        for (int32_t s = 0; s < n_slots; ++s) {
            KeyState& st = state(s);
            if (st.spilled) ::operator delete(st.out.block);
            ::operator delete(st.spare);
        }
        for (KeyState* b : blocks) delete[] b;
        blocks.clear();
        free_slots.clear();
        n_slots = 0;
        n_live = 0;
    }

    inline i64 pane_of(i64 id) const {
        return pshift >= 0 ? id >> pshift : id / pane;
    }

    // the first window that holds `id`
    inline i64 first_window_of(i64 id) const {
        return id < win ? 0 : (id - win) / slide + 1;
    }

    // A key's home slot: its own low bits, doubled.  Keys that follow
    // one another (auction ids, interned ids) then stay neighbours in
    // the table, as under the identity hash, but leave every other slot
    // empty: a run of them is no cluster, so a probe for a new key ends
    // at once and evict()'s backward shift moves nothing.
    inline std::size_t home_of(i64 key) const {
        return ((std::size_t)key << 1) & (tab.size() - 1);
    }

    void grow_table() {
        std::vector<Entry> old;
        old.swap(tab);
        clear_table(old.size() * 4);
        const std::size_t mask = tab.size() - 1;
        for (const Entry& e : old) {
            if (e.slot < 0) continue;
            std::size_t h = home_of(e.key);
            while (tab[h].slot >= 0) h = (h + 1) & mask;
            tab[h] = e;
        }
    }

    // a pool slot for a key that has none: a reused one, else a new one
    int32_t take_slot(i64 key) {
        int32_t s;
        if (!free_slots.empty()) {
            s = free_slots.back();
            free_slots.pop_back();
        } else {
            s = n_slots++;
            if ((std::size_t)(s >> POOL_SHIFT) == blocks.size())
                blocks.push_back(new KeyState[POOL_BLOCK]);
        }
        KeyState& st = state(s);
        st.key = key;
        st.live = true;
        ++keys_opened;
        if (++n_live > keys_live_peak) keys_live_peak = n_live;
        return s;
    }

    // the key's table entry, made (with a fresh slot) where it has
    // none; `opened` says which
    inline Entry& locate(i64 key, bool& opened) {
        const std::size_t mask = tab.size() - 1;
        std::size_t h = home_of(key);
        opened = false;
        while (true) {
            Entry& e = tab[h];
            if (e.slot < 0) {
                if ((n_live + 1) * 4 >= (i64)tab.size()) {
                    grow_table();
                    return locate(key, opened);
                }
                e.key = key;
                e.slot = take_slot(key);
                e.stamp = -1;
                opened = true;
                return e;
            }
            if (e.key == key) return e;
            h = (h + 1) & mask;
        }
    }

    // forget a key: its table entry goes by a backward shift (no
    // tombstone), its slot goes back to the pool
    __attribute__((always_inline)) inline void evict(int32_t slot) {
        KeyState& st = state(slot);
        const std::size_t mask = tab.size() - 1;
        std::size_t i = home_of(st.key);
        while (tab[i].slot != slot) i = (i + 1) & mask;
        while (true) {
            tab[i].slot = -1;
            std::size_t j = i;
            while (true) {
                j = (j + 1) & mask;
                if (tab[j].slot < 0) goto shifted;
                std::size_t k = home_of(tab[j].key);
                // entry j may stay where its home k lies in (i, j]
                if (i <= j ? (i < k && k <= j) : (i < k || k <= j))
                    continue;
                break;
            }
            tab[i] = tab[j];
            i = j;
        }
    shifted:
        if (kind == Kind::ARGMAX) drop_ties(slot, INT64_MAX);
        st.reset();
        free_slots.push_back(slot);
        --n_live;
        ++keys_evicted;
    }

    // the key's index into the per-call arrays, given at its first
    // tuple of the call
    inline int32_t dense_of(i64 key) {
        bool opened;
        Entry& e = locate(key, opened);
        if (e.stamp != call_id) {
            e.stamp = call_id;
            e.dense = (int32_t)parts.size();
            if (opened) opened_now.push_back(e.dense);
            parts.push_back(Part{0, INT64_MAX, INT64_MIN, neutral});
            d_slot.push_back(e.slot);
            KeyState* st = &state(e.slot);
            d_state.push_back(st);
            fetch_state(st);  // prepare() is a walk away
        }
        return e.dense;
    }

    // -- the ring ----------------------------------------------------------
    // panes a key state of this engine holds itself
    // (an ARGMAX ring's `aid` lane has no room inline either)
    inline i64 ring_in() const {
        return is_tb && kind != Kind::ARGMAX ? RING_IN : 0;
    }

    // the lanes of a block of `cap` panes, one behind the other
    // (an ARGMAX block has a fifth, behind the CB lanes' places)
    static inline Ring lanes_of(char* block, i64 cap) {
        double* acc = reinterpret_cast<double*>(block);
        i64* cnt = reinterpret_cast<i64*>(acc + cap);
        return Ring{acc, cnt, cnt + cap, cnt + 2 * cap, cnt + 3 * cap};
    }
    inline Ring ring(KeyState& st) const {
        if (!st.spilled) return Ring{st.in.acc, st.in.cnt, nullptr, nullptr};
        return lanes_of(st.out.block, st.out.cap);
    }
    inline Ring ring(const KeyState& st) const {
        return ring(const_cast<KeyState&>(st));
    }

    // panes [from, to) of the ring hold nothing
    inline void blank(const Ring& r, i64 from, i64 to) const {
        if (from >= to) return;
        std::fill(r.acc + from, r.acc + to, neutral);
        std::fill(r.cnt + from, r.cnt + to, (i64)0);
        if (!is_tb) {
            std::fill(r.lid + from, r.lid + to, INT64_MIN);
            std::fill(r.lts + from, r.lts + to, (i64)0);
        }
        if (kind == Kind::ARGMAX)
            std::fill(r.aid + from, r.aid + to, INT64_MIN);
    }

    // the ring's stored panes moved by `by` places inside its storage
    // (the caller has made room)
    inline void shift(const Ring& r, i64 from, i64 n, i64 by) const {
        if (n <= 0) return;
        std::memmove(r.acc + from + by, r.acc + from, n * sizeof(double));
        std::memmove(r.cnt + from + by, r.cnt + from, n * sizeof(i64));
        if (!is_tb) {
            std::memmove(r.lid + from + by, r.lid + from, n * sizeof(i64));
            std::memmove(r.lts + from + by, r.lts + from, n * sizeof(i64));
        }
        if (kind == Kind::ARGMAX)
            std::memmove(r.aid + from + by, r.aid + from, n * sizeof(i64));
    }

    // The ring into a block of `cap` panes (its `room` stored panes
    // with it): out of the key state, or out of a block it outgrew.
    // The slot's spare block is taken where it is large enough.
    void spill(KeyState& st, i64 cap) {
        const Ring old = ring(st);
        const i64 lanes = kind == Kind::ARGMAX ? 5 : is_tb ? 2 : 4;
        char* block;
        if (st.spare_cap >= cap) {
            block = st.spare;
            cap = st.spare_cap;
            st.spare = nullptr;
            st.spare_cap = 0;
        } else {
            block = static_cast<char*>(
                ::operator new((std::size_t)(cap * lanes) * sizeof(i64)));
        }
        const Ring now = lanes_of(block, cap);
        if (st.room > 0) {
            std::memcpy(now.acc, old.acc, st.room * sizeof(double));
            std::memcpy(now.cnt, old.cnt, st.room * sizeof(i64));
            if (!is_tb) {
                std::memcpy(now.lid, old.lid, st.room * sizeof(i64));
                std::memcpy(now.lts, old.lts, st.room * sizeof(i64));
            }
            if (kind == Kind::ARGMAX)
                std::memcpy(now.aid, old.aid, st.room * sizeof(i64));
        }
        if (st.spilled)
            ::operator delete(st.out.block);
        else
            ++rings_spilled;
        st.spilled = true;
        st.out.block = block;
        st.out.cap = cap;
    }

    inline i64 capacity(const KeyState& st) const {
        return st.spilled ? st.out.cap : ring_in();
    }

    // a ring's new length, where a key state can state it
    static inline i64 checked(i64 len) {
        if (len > INT32_MAX) throw std::bad_alloc();
        return len;
    }

    // the ring's length set to `len` panes, the stored ones among them
    // blank from `used` on
    inline void stretch(KeyState& st, i64 len, i64 used) {
        st.len = (int32_t)len;
        const i64 room = std::min(len, capacity(st));
        blank(ring(st), used, room);
        st.room = (int32_t)room;
    }

    // grow the pane ring so relative pane p_rel is addressable
    inline void ensure_pane(KeyState& st, i64 p_rel) {
        if (p_rel < st.room) return;
        grow_ring(st, p_rel);
    }

    void grow_ring(KeyState& st, i64 p_rel) {
        // geometric headroom: rings grow a few panes per batch; the
        // +8 keeps amortized growth O(1) without doubling a large ring
        i64 len = st.len;
        if (p_rel >= len)
            len = checked(p_rel + 1 + std::min<i64>(p_rel / 2 + 8, 4096));
        if (p_rel >= capacity(st)) spill(st, len);
        stretch(st, len, st.room);
    }

    // CB lanes: the pane's last tuple (by id) and its stamp
    inline void stamp_last(KeyState& st, i64 p_rel, i64 id, i64 ts) {
        const Ring r = ring(st);
        if (id >= r.lid[p_rel]) {
            r.lid[p_rel] = id;
            r.lts[p_rel] = ts;
        }
    }

    inline void fold(KeyState& st, i64 p_rel, double v) {
        const Ring r = ring(st);
        switch (kind) {
            case Kind::COUNT: r.acc[p_rel] += 1.0; break;
            case Kind::MAX:
                if (v > r.acc[p_rel]) r.acc[p_rel] = v;
                break;
            case Kind::MIN:
                if (v < r.acc[p_rel]) r.acc[p_rel] = v;
                break;
            case Kind::SUM:
            case Kind::MEAN:
            default: r.acc[p_rel] += v; break;
        }
        ++r.cnt[p_rel];
    }

    // -- firing -----------------------------------------------------------
    // Whether window [start, start + win) of the key holds a tuple.
    // `q` is a cursor over the ring (the first pane at or after the
    // last window's start that holds one), so a run of windows costs
    // one pass over their panes.
    inline bool holds_tuple(const KeyState& st, i64 start, i64& q) const {
        i64 ps = pane_of(start) - st.pane_base;
        i64 pe = std::min<i64>(ps + ppw, st.room);
        const i64* cnt = ring(st).cnt;
        if (q < ps) q = ps < 0 ? 0 : ps;
        while (q < pe && cnt[q] == 0) ++q;
        return q < pe;
    }

    // Queue every window of the key that `front` has passed (the one
    // place the rule is applied: stream time, a CB key's own largest
    // id, or everything at EOS).
    inline void fire_key(KeyState& st, int32_t slot, i64 front) {
        i64 q = -1;
        while (st.next_fire <= st.opened_max) {
            const i64 start = st.next_fire * slide;
            if (front - delay < start + win) break;
            if (!sparse || holds_tuple(st, start, q)) {
                ready.push_back(Desc{st.key, st.next_fire, slot});
                ++st.queued;
                ++windows_fired;
            }
            ++st.next_fire;
        }
    }

    // list the key under the window it fires next
    inline void index_key(KeyState& st, int32_t slot) {
        if (st.indexed || st.next_fire > st.opened_max) return;
        due[st.next_fire].push_back(slot);
        st.due_at = st.next_fire;
        st.indexed = true;
    }

    // The stream has moved: fire the windows it has passed, for the
    // keys listed under them alone.
    void trigger() {
        const i64 t = stream_time - delay - win;
        const i64 upto = t < 0 ? -1 : t / slide;
        if (upto > fired_upto) fired_upto = upto;
        if (due.empty() || due.begin()->first > fired_upto) return;
        const i64 t0 = now_ns();
        while (!due.empty() && due.begin()->first <= fired_upto) {
            // taken out first: a key that still has a window opened is
            // listed anew, under a later one
            const auto node = due.extract(due.begin());
            const std::vector<int32_t>& slots = node.mapped();
            const std::size_t ns = slots.size();
            const bool ahead = runs_ahead();
            for (std::size_t i = 0; i < ns; ++i) {
                const int32_t slot = slots[i];
                if (ahead) {
                    // all three lines: the listing is in the last
                    if (i + 12 < ns) {
                        const KeyState* nx = &state(slots[i + 12]);
                        fetch_state(nx);
                        fetch(&nx->due_at);
                    }
                    if (i + 4 < ns) {
                        const KeyState& nx = state(slots[i + 4]);
                        fetch_ring(nx, pane_of(nx.next_fire * slide)
                                   - nx.pane_base);
                    }
                }
                KeyState& st = state(slot);
                // a listing its key left behind (move_back), or that a
                // later one of the same window has served
                if (!st.indexed || st.due_at != node.key()) continue;
                st.indexed = false;
                fire_key(st, slot, stream_time);
                index_key(st, slot);
                if (sparse && st.queued == 0 && !st.indexed)
                    evict(slot);  // every window it opened lay empty
            }
        }
        trigger_ns += now_ns() - t0;
    }

    // the id below which a tuple is late for every key: the end of the
    // last window the stream has passed (a key that was evicted must
    // not open that window again)
    inline i64 stream_accept() const {
        return stream_rule && fired_upto >= 0 ? fired_upto * slide + win
                                              : INT64_MIN;
    }

    // a key's first data (of this life): anchor the fire frontier at
    // the first window containing the earliest tuple of the call --
    // firing from 0 on an epoch-scale first id/ts would emit ~id/slide
    // empty windows (flood/OOM) -- and never at a window the stream has
    // passed.  Under the stream rule the anchor is where the key stands
    // now, not a promise: the first tuple of a key to arrive need not be
    // its earliest (move_back).
    inline void anchor_key(KeyState& st, i64 first) {
        i64 a = first_window_of(first);
        if (stream_rule && a <= fired_upto) a = fired_upto + 1;
        st.anchor = st.next_fire = a;
        st.pane_base = pane_of(a * slide);
    }

    // A tuple of a live key that belongs to window `w`, before the one
    // the key fires next, and to no window the stream has passed
    // (w > fired_upto): the key's first tuple to arrive was not its
    // earliest, or the empty windows in front of a returning key's
    // tuple were skipped too soon.  The key fires from `w`, its ring
    // grows at the front to hold w's panes (what was cut there held
    // nothing: every window from the key's last opened one on lay
    // empty), and settle() lists it under `w`; its old listing stays
    // in `due` and is passed over.  Timed with `open`.
    void move_back(KeyState& st, i64 w) {
        const i64 t0 = now_ns();
        st.next_fire = w;
        if (w < st.anchor) st.anchor = w;
        const i64 k = st.pane_base - pane_of(w * slide);
        if (k > 0) {
            // the stored panes that hold a tuple move up by k; where
            // they then outgrow the storage the ring leaves for a block
            i64 used = st.room;
            const i64* cnt = ring(st).cnt;
            while (used > 0 && cnt[used - 1] == 0) --used;
            const i64 len = checked((i64)st.len + k);
            if (used + k > capacity(st)) spill(st, len);
            const Ring r = ring(st);
            shift(r, 0, used, k);
            blank(r, 0, k);
            stretch(st, len, used + k);
            st.pane_base -= k;
        }
        st.indexed = false;
        ++anchors_moved;
        open_ns += now_ns() - t0;
    }

    // the end of the window before the one the key fires next: a tuple
    // below it belongs to a window the key has left behind
    inline i64 left_behind(const KeyState& st) const {
        return st.next_fire > 0 ? (st.next_fire - 1) * slide + win
                                : INT64_MIN;
    }

    // the acceptance boundary of a key: tuples below it fall in a window
    // that has fired and are counted as ignored.  Under the stream rule
    // the stream alone decides (the end of the last window it passed: a
    // key fires no window the stream has not passed); a CB or renumbered
    // key fires on its own ids, and its boundary is its own last fired
    // window's end
    inline i64 accept_of(const KeyState& st) const {
        if (stream_rule) return stream_accept();
        return st.next_fire > st.anchor ? left_behind(st) : INT64_MIN;
    }

    // per key and call, before the fold: this batch's id range, the
    // anchor of a new key, the acceptance boundary, the ring's room
    // (ARG: an ARGMAX engine's call, "THE IDS AT A PANE'S MAXIMUM")
    template <bool ARG = false>
    inline void prepare(std::size_t d) {
        KeyState& st = *d_state[d];
        Part& pt = parts[d];
        if (renumber) {
            // implicit arrival-order ids: this batch appends ids
            // [arrivals, arrivals + count)
            pt.lo = st.arrivals;
            pt.hi = st.arrivals + pt.count - 1;
        }
        bool look = false;
        if (st.max_id < 0) {
            anchor_key(st, pt.lo);
        } else if (stream_rule) {
            if (pt.lo >= stream_accept()) {
                if (pt.lo < left_behind(st)) {
                    // the call's earliest tuple of the key lies before
                    // the window the key fires next
                    move_back(st, first_window_of(pt.lo));
                } else if (sparse && st.next_fire > st.opened_max) {
                    // every window the key opened has fired: the
                    // windows that lie empty before this call's first
                    // tuple are skipped here, not one by one at the
                    // trigger
                    const i64 w0 = first_window_of(pt.lo);
                    if (w0 > st.next_fire) st.next_fire = w0;
                }
            } else {
                // a late tuple hides the earliest one that counts:
                // fold_singly() looks at each
                look = true;
            }
        }
        d_accept[d] = accept_of(st);
        // pre-grow the ring to this batch's frontier so the fold
        // never reallocates
        i64 hi_rel = pane_of(pt.hi) - st.pane_base;
        if (hi_rel >= 0) ensure_pane(st, hi_rel);
        fold_key<ARG>(d, st, pt, hi_rel);
        if (look) d_single[d] = 2;
    }

    // The key's partial of this call into its pane with one combine,
    // where that gives the bits the one-by-one fold would: every tuple
    // of the key accepted and in the one pane `hi_rel`, on a lane whose
    // pane state is a count and an order-free value.  COUNT adds whole
    // numbers (exact in a double), MAX / MIN keep the first of equal
    // extremes as the one-by-one fold does.  Else the key is marked and
    // its tuples take the second walk.
    // ARGMAX keeps the call's largest value and the ids at it
    // (keep_arg).
    template <bool ARG = false>
    inline void fold_key(std::size_t d, KeyState& st, const Part& pt,
                         i64 hi_rel) {
        const bool whole = by_key_lane && pt.lo >= d_accept[d]
            && hi_rel >= 0 && pane_of(pt.lo) - st.pane_base == hi_rel;
        d_single[d] = !whole;
        if (!whole) {
            ++n_single;
            return;
        }
        const Ring r = ring(st);
        if (ARG) {
            keep_arg(st, d_slot[d], hi_rel, arg_at[d]);
        } else {
            double& acc = r.acc[hi_rel];
            switch (kind) {
                case Kind::COUNT: acc += (double)pt.count; break;
                case Kind::MAX: if (pt.ext > acc) acc = pt.ext; break;
                default: if (pt.ext < acc) acc = pt.ext; break;  // MIN
            }
        }
        r.cnt[hi_rel] += pt.count;
        folded_by_key += pt.count;
    }

    // per key and call, after the fold: the frontier it opened, and
    // its part in the firing
    inline void settle(KeyState& st, int32_t slot, i64 max_seen) {
        if (max_seen > st.max_id) st.max_id = max_seen;
        if (win >= slide && st.max_id >= 0) {
            i64 last_w = (st.max_id + 1 + slide - 1) / slide - 1;
            if (last_w > st.opened_max) st.opened_max = last_w;
        }
        if (stream_rule) {
            if (st.max_id > stream_time) stream_time = st.max_id;
            index_key(st, slot);
        } else {
            fire_key(st, slot, st.max_id);
        }
    }

    // A call's selection (a filtered batch that carries its rows instead
    // of copies of its columns, core/tuples.py): row j of the call is
    // row rows[j] of each column whose bit is set in `through` (1 keys,
    // 2 ids, 4 stamps, 8 values); a column whose bit is clear is compact
    // already (a map laid a new one over the selection).  The walks take
    // SEL as a template parameter: without a selection they compile to
    // the code they were before there was one.
    struct Sel {
        const i64* rows;
        int through;
    };
    enum : int { SEL_KEYS = 1, SEL_IDS = 2, SEL_TSS = 4, SEL_VALS = 8 };

    // The one walk over a call's tuples: a probe each, and the key's
    // partial brought up to date.  IDS: the ids are read (renumbered
    // ids are implicit); EXT: 1 keeps the largest value, 2 the
    // smallest, 0 reads no value.
    // AHEAD (the table has outgrown the caches, "WHERE A CALL RUNS
    // AHEAD OF ITSELF"): while one block of tuples is walked the table
    // records of the next are fetched, block-wise and not a tuple; a
    // call that does not run ahead is the plain loop.
    // The walk's body over tuples [from, to), the one both walks run:
    // `at.index(key)` is the key's index into `at`'s partials (the
    // engine's: dense_of(); a slice's: its own table), kept in
    // `at.slot(j)`.  ck / ci / cv: the column is compact (SEL).
    // (inlined by force: the loop is the walk, and where GCC leaves it
    // out of line its caller's constants stop at the call)
    template <bool IDS, int EXT, bool SEL, typename TV, typename At>
    __attribute__((always_inline)) static inline void walk_tuples(At& at, const i64* bkeys, const i64* ids,
                                   const TV* vals, Sel s, bool ck, bool ci,
                                   bool cv, i64 from, i64 to) {
        for (i64 j = from; j < to; ++j) {
            const i64 r = SEL ? s.rows[j] : j;
            const int32_t d = at.index(bkeys[ck ? j : r]);
            at.slot(j) = d;
            Part& pt = at.part(d);
            ++pt.count;
            if (IDS) {
                const i64 id = ids[ci ? j : r];
                if (id < pt.lo) pt.lo = id;
                if (id > pt.hi) pt.hi = id;
            }
            if (EXT == 1) {
                const double v = (double)vals[cv ? j : r];
                if (v > pt.ext) pt.ext = v;
            } else if (EXT == 2) {
                const double v = (double)vals[cv ? j : r];
                if (v < pt.ext) pt.ext = v;
            }
        }
    }
    // gather()'s `at`: the engine's table and per-call arrays
    struct Own {
        Engine& e;
        inline int32_t index(i64 key) { return e.dense_of(key); }
        inline int32_t& slot(i64 j) { return e.slot_of[j]; }
        inline Part& part(int32_t d) { return e.parts[d]; }
    };

    // (ARG: an argmax engine's own instantiation of the same walk, so
    // that each of the other kinds' keeps its one caller, and with it
    // the compiler's inlining into that caller; TUP a tuples engine's)
    template <bool IDS, int EXT, bool SEL, bool AHEAD, bool LANES = false,
              bool ARG = false, bool TUP = false, typename TV>
    void gather(const i64* bkeys, const i64* ids, const TV* vals, i64 n,
                Sel s) {
        if (LANES) {
            gather_in_slices<IDS, EXT, SEL, ARG>(bkeys, ids, vals, n, s);
            return;
        }
        const bool ck = SEL && !(s.through & SEL_KEYS);   // compact columns
        const bool ci = SEL && !(s.through & SEL_IDS);
        const bool cv = SEL && !(s.through & SEL_VALS);
        Own own{*this};
        auto walk = [&](i64 from, i64 to) {
            walk_tuples<IDS, EXT, SEL>(own, bkeys, ids, vals, s, ck, ci, cv,
                                       from, to);
        };
        if (!AHEAD || !probes_ahead) {
            walk(0, n);
            return;
        }
        // the table may grow under the walk: a record fetched from the
        // old one is a fetch wasted, no more
        auto fetch_records = [&](i64 from, i64 to) {
            for (i64 j = from; j < to; ++j)
                fetch(&tab[home_of(bkeys[ck || !SEL ? j : s.rows[j]])]);
        };
        fetch_records(0, std::min<i64>(AHEAD_BLOCK, n));
        for (i64 b = 0; b < n; b += AHEAD_BLOCK) {
            const i64 e = std::min<i64>(b + AHEAD_BLOCK, n);
            fetch_records(e, std::min<i64>(e + AHEAD_BLOCK, n));
            walk(b, e);
        }
    }
    static constexpr i64 AHEAD_BLOCK = 64;

    // THE TUPLE WALK IN SLICES.  Where a call's table fits the caches
    // (the walk does not run ahead), the call is long enough (LANES_FROM)
    // and comes soon after the last (LANES_GAP_NS), gather() is split
    // over the process's host lanes
    // (namespace lanes): each takes a contiguous slice of the call's
    // tuples and walks it with the same body, into partials of its own
    // under a table of its own, never the engine's, in the order of
    // first touch inside the slice.  The caller then merges, slice by
    // slice in order: each key of a slice through dense_of(), which
    // gives it its index in the order of its first tuple over the whole
    // call and opens it where the table has no record (so the table,
    // parts, d_slot, d_state and opened_now come out as the one walk
    // leaves them), and its partial into the call's (counts add, ids
    // and extremes by min and max, the first of equal extremes kept);
    // slot_of is left in each slice's own indices, and made the call's
    // on the lanes where the second walk reads it (fold_singly(); a call
    // whose keys all fold by key never does).  Counts are integers and
    // the rest is order-free: what the walks after gather() read is the
    // one walk's, bit for bit.
    // No knob: what decides is the table's size, the call's length, how
    // soon it came after the last and the cores the process may run on
    // (ingest_batch, in_slices()).  Counted:
    // walked_in_lanes, the tuples of calls walked so.
    struct Slice {
        struct Rec {
            i64 key;
            i64 stamp;                // the slice's call that wrote it
            int32_t at;
        };
        std::vector<Rec> tab;         // open addressing from the key, doubled
        i64 stamp = 0;
        std::vector<Part> parts;      // index = order of first touch
        std::vector<i64> keys;
        std::vector<int32_t> call_of; // index -> the call's (the merge's)
        int32_t* slot_of = nullptr;
        double neutral = 0.0;

        void begin(std::size_t size, int32_t* so, double nt) {
            if (tab.size() < size) tab.assign(size, Rec{0, 0, 0});
            ++stamp;
            parts.clear();
            keys.clear();
            slot_of = so;
            neutral = nt;
        }
        void grow() {
            tab.assign(tab.size() * 4, Rec{0, 0, 0});
            const std::size_t mask = tab.size() - 1;
            for (std::size_t i = 0; i < keys.size(); ++i) {
                std::size_t h = ((std::size_t)keys[i] << 1) & mask;
                while (tab[h].stamp == stamp) h = (h + 1) & mask;
                tab[h] = Rec{keys[i], stamp, (int32_t)i};
            }
        }
        inline int32_t index(i64 key) {
            const std::size_t mask = tab.size() - 1;
            std::size_t h = ((std::size_t)key << 1) & mask;
            while (true) {
                Rec& r = tab[h];
                if (r.stamp != stamp) {
                    if (((i64)keys.size() + 1) * 4 >= (i64)tab.size()) {
                        grow();
                        return index(key);
                    }
                    r = Rec{key, stamp, (int32_t)keys.size()};
                    keys.push_back(key);
                    parts.push_back(Part{0, INT64_MAX, INT64_MIN, neutral});
                    return r.at;
                }
                if (r.key == key) return r.at;
                h = (h + 1) & mask;
            }
        }
        inline int32_t& slot(i64 j) { return slot_of[j]; }
        inline Part& part(int32_t d) { return parts[d]; }
    };
    // A split call pays, whatever its length, the lanes' wake, the last
    // slice's tail and the merge (60-90 us on the chip's host) and the
    // caller's own work about the call slowing by some 40-100 us more,
    // against some 4 ns a tuple saved: it gains from some 30,000-45,000
    // tuples on (PERF.md section 6, PR 39: at 65,536 the 111-key cell
    // gained 21 %, at 21,800 selected rows the YSB cell lost 12.6 %)
    static constexpr i64 LANES_FROM = 49152;
    // ... and where the caller feeds the engine as fast as it can: its
    // last call ended less than LANES_GAP_NS before this one (0.1-0.3 ms
    // between calls in the saturated cells).  A caller that waits for
    // its input between calls (5 ms in the paced cell, 2.5 ms inside a
    // burst) walks alone: it has the time, and on the chip's host a
    // lane woken from sleep costs more CPU than the walk it takes over
    // (PERF.md section 6, PR 39)
    static constexpr i64 LANES_GAP_NS = 1000000;
    // One slice a lane, for the process's one call at a time (the lanes'
    // hold), and which call left slot_of in its slices' own indices:
    // the merge leaves them there, and they are made the call's where a
    // walk reads them (fold_singly(), ingest_walks) -- a call whose keys
    // all fold by key never does.  Never freed, as the pool is not.
    struct Slices {
        std::vector<Slice> of = std::vector<Slice>(lanes::MAX);
        const Engine* owner = nullptr;
        i64 call = -1;                // the owner's call_id
        i64 n = 0;
        int pieces = 0;
    };
    static Slices& slices() {
        static Slices* s = new Slices;
        return *s;
    }

    // a lane-split call's walk: slice k
    template <bool IDS, int EXT, bool SEL, typename TV>
    struct SliceWalk {
        Engine* e;
        const i64* bkeys;
        const i64* ids;
        const TV* vals;
        i64 n;
        Sel s;
        int pieces;
        bool ck, ci, cv;
        static void walk(void* p, int k) {
            const SliceWalk& w = *static_cast<const SliceWalk*>(p);
            Slice& sl = slices().of[k];
            sl.begin(w.e->tab.size(), w.e->slot_of.data(), w.e->neutral);
            walk_tuples<IDS, EXT, SEL>(sl, w.bkeys, w.ids, w.vals, w.s, w.ck,
                                       w.ci, w.cv, w.n * k / w.pieces,
                                       w.n * (k + 1) / w.pieces);
        }
    };
    // ... and slice k + 1's indices in slot_of made the call's (a mark
    // note_late() left in the sign stays)
    static void to_the_call(void* p, int k) {
        const Slices& ss = *static_cast<const Slices*>(p);
        const Slice& sl = ss.of[k + 1];
        const int32_t* to = sl.call_of.data();
        int32_t* so = sl.slot_of;
        for (i64 j = ss.n * (k + 1) / ss.pieces,
                 e = ss.n * (k + 2) / ss.pieces; j < e; ++j)
            so[j] = to[so[j] & ~LATE_MARK] | (so[j] & LATE_MARK);
    }

    template <bool IDS, int EXT, bool SEL, bool ARG, typename TV>
    void gather_in_slices(const i64* bkeys, const i64* ids, const TV* vals,
                          i64 n, Sel s) {
        const lanes::Hold& hold = *lanes_hold;
        Slices& ss = slices();
        int pieces = hold.pool != nullptr ? hold.pool->lanes : lanes::MAX;
        if (n < pieces) pieces = (int)n;
        if (pieces < 2) {
            gather<IDS, EXT, SEL, false, false, ARG>(bkeys, ids, vals, n, s);
            return;
        }
        SliceWalk<IDS, EXT, SEL, TV> job{
            this, bkeys, ids, vals, n, s, pieces,
            SEL && !(s.through & SEL_KEYS), SEL && !(s.through & SEL_IDS),
            SEL && !(s.through & SEL_VALS)};
        hold.run(&decltype(job)::walk, &job, pieces);
        for (int k = 0; k < pieces; ++k) {
            Slice& sl = ss.of[k];
            const std::size_t nk = sl.keys.size();
            sl.call_of.resize(nk);
            for (std::size_t i = 0; i < nk; ++i) {
                const int32_t d = dense_of(sl.keys[i]);
                sl.call_of[i] = d;
                Part& pt = parts[d];
                const Part& q = sl.parts[i];
                pt.count += q.count;
                if (q.lo < pt.lo) pt.lo = q.lo;
                if (q.hi > pt.hi) pt.hi = q.hi;
                if (EXT == 1 && q.ext > pt.ext) pt.ext = q.ext;
                if (EXT == 2 && q.ext < pt.ext) pt.ext = q.ext;
            }
        }
        // slice 0's indices are the call's already (its keys came first);
        // the others' wait for a reader
        ss.owner = this;
        ss.call = split_call = call_id;
        ss.n = n;
        ss.pieces = pieces;
        walked_in_lanes += n;
    }

    // slot_of as the one walk leaves it, where this engine's last call
    // left it in its slices' indices: on the lanes during the call, on
    // the caller after it (the test-only view, wfn_engine_last_call);
    // false where another call has had the slices since
    bool slots_of_the_call() {
        if (split_call != call_id) return true;
        Slices& ss = slices();
        if (ss.owner != this || ss.call != call_id) return false;
        if (lanes_hold != nullptr)
            lanes_hold->run(&to_the_call, &ss, ss.pieces - 1);
        else
            for (int k = 0; k + 1 < ss.pieces; ++k) to_the_call(&ss, k);
        ss.owner = nullptr;
        split_call = -1;
        return true;
    }

    // Which of the call's tuples lie behind the stream time as they
    // come (the largest stamp of every tuple before them): counted, and
    // marked in slot_of's sign for fold_singly(), which knows whether
    // they stay.  gather()'s walk is bound by what it does a tuple, so
    // this is a walk of its own, over the ids it has just read: first
    // whether the stamps run in order from the stream time on (block by
    // block, no branch a tuple: an in-order stream pays that and no
    // more), and only where they do not, each tuple against the running
    // stream time.
    static constexpr int32_t LATE_MARK = INT32_MIN;
    template <bool THROUGH>
    void note_late(const i64* ids, const i64* rows, i64 n) {
        if (n == 0) return;
        auto at = [&](i64 j) { return ids[THROUGH ? rows[j] : j]; };
        bool sorted = at(0) >= stream_time;
        for (i64 b = 1; sorted && b < n; b += 4096) {
            const i64 e = std::min<i64>(b + 4096, n);
            i64 back = 0;
            for (i64 j = b; j < e; ++j) back += at(j) < at(j - 1);
            sorted = back == 0;
        }
        if (sorted) return;
        i64 front = stream_time, late = 0;
        for (i64 j = 0; j < n; ++j) {
            const i64 id = at(j);
            const int32_t behind = id < front;
            late += behind;
            slot_of[j] |= -behind & LATE_MARK;
            front = id > front ? id : front;
        }
        late_seen += late;
    }

    // TV = double or float: f32 sources fold without a host-side
    // widening copy (values widen at the accumulate)
    template <bool SEL, typename TV>
    void ingest_batch(const i64* bkeys, const i64* ids, const i64* tss,
                      const TV* vals, i64 n, Sel s = Sel{nullptr, 0}) {
        if (kind == Kind::ARGMAX) {
            ingest_argmax<SEL>(bkeys, ids, tss, vals, n, s);
            return;
        }
        if (kind == Kind::TUPLES) {
            ingest_tuples<SEL>(bkeys, ids, tss, vals, n, s);
            return;
        }
        if (runs_ahead())
            ingest_walks<SEL, true>(bkeys, ids, tss, vals, n, s);
        else if (ingest_one_key<SEL>(bkeys, ids, tss, vals, n, s))
            return;
        else if (in_slices(n))
            ingest_in_slices<SEL>(bkeys, ids, tss, vals, n, s);
        else
            ingest_walks<SEL, false>(bkeys, ids, tss, vals, n, s);
    }

    // out of line: the entry points keep the code they had (PR 37's
    // lesson, "THE INSIDE OF A BATCH CALL" below)
    // The lanes are held for the whole call (the slices keep what the
    // merge left until the call is done with it); a call that finds
    // them held by another engine's walks as one.
    template <bool SEL, typename TV>
    __attribute__((noinline)) void ingest_in_slices(
            const i64* bkeys, const i64* ids, const i64* tss, const TV* vals,
            i64 n, Sel s) {
        const lanes::Hold hold;
        if (!hold.held) {
            ingest_walks<SEL, false>(bkeys, ids, tss, vals, n, s);
            return;
        }
        lanes_hold = &hold;
        ingest_walks<SEL, false, true>(bkeys, ids, tss, vals, n, s);
        lanes_hold = nullptr;
    }

    template <bool SEL, bool AHEAD, bool LANES = false, bool ARG = false,
              bool TUP = false, typename TV>
    void ingest_walks(const i64* bkeys, const i64* ids, const i64* tss,
                      const TV* vals, i64 n, Sel s) {
        call_begins();
        ++call_id;
        parts.clear();
        d_slot.clear();
        d_state.clear();
        opened_now.clear();
        if ((i64)slot_of.size() < n) slot_of.resize(n);
        if constexpr (TUP) {  // COUNT's walk, in an instantiation of its own
            if (renumber)
                gather<false, 0, SEL, AHEAD, false, false, true>(bkeys, ids,
                                                                 vals, n, s);
            else
                gather<true, 0, SEL, AHEAD, false, false, true>(bkeys, ids,
                                                                vals, n, s);
        } else if (ARG) {  // COUNT's walk, in an instantiation of its own
            if (renumber)
                gather<false, 0, SEL, AHEAD, LANES, true>(bkeys, ids, vals, n,
                                                          s);
            else
                gather<true, 0, SEL, AHEAD, LANES, true>(bkeys, ids, vals, n,
                                                         s);
        } else if (renumber)
            gather<false, 0, SEL, AHEAD, LANES>(bkeys, ids, vals, n, s);
        else if (by_key_lane && kind == Kind::MAX)
            gather<true, 1, SEL, AHEAD, LANES>(bkeys, ids, vals, n, s);
        else if (by_key_lane && kind == Kind::MIN)
            gather<true, 2, SEL, AHEAD, LANES>(bkeys, ids, vals, n, s);
        else
            gather<true, 0, SEL, AHEAD, LANES>(bkeys, ids, vals, n, s);
        if (stream_rule) {
            if (SEL && (s.through & SEL_IDS))
                note_late<true>(ids, s.rows, n);
            else
                note_late<false>(ids, nullptr, n);
        }
        piece_ends(tuple_walk_ns);
        if (ARG) {
            argmax_of_the_call<SEL, LANES>(is_tb ? tss : ids, vals, n, s);
            piece_ends(argmax_ns);
        }
        const std::size_t nd = parts.size();
        d_accept.resize(nd);
        d_single.resize(nd);
        n_single = 0;
        key_touches += (i64)nd;
        if (AHEAD) walked_ahead += (i64)nd;
        // the visit by slot: a key's state some keys ahead (dense_of()
        // fetched it a walk ago; it may have left the inner caches
        // since), a ring outside its key state a few keys ahead
        auto ahead_of = [&](std::size_t d) {
            if (!AHEAD) return;
            if (d + 12 < nd) fetch_state(d_state[d + 12]);
            if (d + 4 < nd) {
                const KeyState& nx = *d_state[d + 4];
                fetch_ring(nx, pane_of(parts[d + 4].hi) - nx.pane_base);
            }
        };
        if (opened_now.empty()) {
            for (std::size_t d = 0; d < nd; ++d) {
                ahead_of(d);
                prepare<ARG>(d);
            }
        } else {
            // `open`: the keys this call created, timed apart (their
            // anchor and their ring); the others after them
            const i64 t0 = now_ns();
            for (int32_t d : opened_now) prepare<ARG>(d);
            open_ns += now_ns() - t0;
            std::size_t o = 0;  // opened_now is ascending
            for (std::size_t d = 0; d < nd; ++d) {
                ahead_of(d);
                if (o < opened_now.size() && (std::size_t)opened_now[o] == d)
                    ++o;
                else
                    prepare<ARG>(d);
            }
        }
        probes_ahead = (i64)(nd - opened_now.size()) * AHEAD_BLOCK > n;
        // `open` inside the visit by key: the keys the call created and
        // the anchors prepare() moved back; inside the second walk: the
        // anchors fold_singly() moved back
        piece_ends(key_walk_ns);
        if (LANES && n_single) slots_of_the_call();
        if constexpr (TUP) fresh_series();
        if (n_single) fold_singly<SEL, ARG, TUP>(ids, tss, vals, n, s);
        if (ARG)
            piece_ends(argmax_ns);
        else
            piece_ends(tuple_walk_ns);
        for (std::size_t d = 0; d < nd; ++d) {
            if (AHEAD && d + 8 < nd) fetch(d_state[d + 8]);
            settle(*d_state[d], d_slot[d], parts[d].hi);
        }
        piece_ends(key_walk_ns);
        if (stream_rule) trigger();
        call_ends();
    }

    // The second walk, for the keys fold_key() left: each of their
    // tuples against the acceptance boundary and into its own pane.
    template <bool SEL, bool ARG = false, bool TUP = false, typename TV>
    void fold_singly(const i64* ids, const i64* tss, const TV* vals, i64 n,
                     Sel s) {
        // hopping windows (win < slide): whether an id opens a window
        // depends on its position inside the slide period, so the
        // opened-window frontier must be tracked per accepted tuple --
        // the batch's final max_id alone misses windows opened by
        // mid-batch ids when the batch ends in a gap
        const bool hopping = win < slide;
        const bool ci = SEL && !(s.through & SEL_IDS);
        const bool ct = SEL && !(s.through & SEL_TSS);
        const bool cv = SEL && !(s.through & SEL_VALS);
        i64 folded = 0;
        for (i64 j = 0; j < n; ++j) {
            const int32_t d = slot_of[j] & ~LATE_MARK;
            if (!d_single[d]) continue;
            const bool late = slot_of[j] < 0;
            const i64 r = SEL ? s.rows[j] : j;
            KeyState& st = *d_state[d];
            const i64 id = renumber ? st.arrivals++ : ids[ci ? j : r];
            if (!renumber && id < d_accept[d]) {
                ++ignored;
                late_dropped += late;
                continue;
            }
            const i64 nn = hopping ? id / slide : 0;
            if (hopping && id >= nn * slide + win) {
                // a gap tuple belongs to no window: nothing is owed it
                late_dropped += late;
                continue;
            }
            if (d_single[d] == 2 && id < left_behind(st))
                move_back(st, first_window_of(id));
            const i64 p = pane_of(id) - st.pane_base;
            if (p < 0) {
                // below the anchor of a key that cannot move it (CB
                // windows)
                ++ignored;
                continue;
            }
            if (hopping && nn > st.opened_max) st.opened_max = nn;
            if constexpr (TUP)
                keep_tuple(st, d_slot[d], p, (double)vals[cv ? j : r], id);
            else if (ARG)  // a TB tuple's own id rides in the stamps' place
                fold_arg(st, d_slot[d], p, (double)vals[cv ? j : r],
                         is_tb ? tss[ct ? j : r] : id);
            else
                fold(st, p, (double)vals[cv ? j : r]);
            ++folded;
            if (!is_tb) stamp_last(st, p, id, tss[ct ? j : r]);
        }
        folded_singly += folded;
    }

    // Fused synthesis + ingest: generate events [start, start+n) of the
    // declared synthetic law (key = e % K, id = ts = e / K,
    // value = (e % vmod) * vscale + voff -- operators/synth.py) and
    // fold them directly into the pane rings.  Grouping by key turns
    // the per-tuple hash probe into one table lookup per key, and the
    // generated columns never materialize in memory: the host feed for
    // a declared synthetic stream costs the fold alone, the columnar
    // twin of the record plane's set_synth lane.
    // ``mask``: optional residue filter (uint8[vmod]; entry 0 drops) --
    // a declared value-predicate filter folds to it, since the
    // synthetic value of event e depends only on e % vmod.  A dropped
    // event behaves exactly as if a Filter removed it before the
    // window op: it does not fold, does not advance max_id/arrivals or
    // the stream time, and cannot open or trigger windows (the record
    // plane's EOS fires only up to the last SURVIVING tuple).
    // ``vtab``: optional per-residue value table (double[vmod])
    // computed by applying the declared map chain sequentially --
    // bit-identical floats to the per-event path, where composing the
    // affines into one (vscale, voff) could differ by ULPs at filter
    // boundaries.
    void synth_ingest(i64 start, i64 n, i64 K, i64 vmod,
                      double vscale, double voff,
                      const unsigned char* mask = nullptr,
                      const double* vtab = nullptr) {
        const i64 endE = start + n;
        const bool hopping = win < slide;
        if (vmod <= 0) vmod = 1;
        const i64 kmod = K % vmod;
        for (i64 k = 0; k < K; ++k) {
            // first event e >= start with e % K == k
            i64 e0 = start + (((k - start % K) % K) + K) % K;
            if (e0 >= endE) continue;
            bool opened;
            const int32_t slot = locate(k, opened).slot;
            KeyState& st = state(slot);
            const i64 id0 = e0 / K;
            const i64 cnt = (endE - e0 + K - 1) / K;
            if (st.max_id < 0 && !mask) {
                anchor_key(st, id0);
            } else if (st.max_id < 0 && mask) {
                // anchor on the first SURVIVING id (a masked prefix
                // must not open windows the record plane never sees)
                i64 vm0 = e0 % vmod;
                i64 first = -1;
                for (i64 j = 0; j < cnt; ++j) {
                    if (mask[vm0]) { first = id0 + j; break; }
                    vm0 += kmod;
                    if (vm0 >= vmod) vm0 -= vmod;
                }
                if (first < 0) continue;  // whole chunk filtered out
                anchor_key(st, first);
            }
            i64 hi_rel = pane_of(id0 + cnt - 1) - st.pane_base;
            if (hi_rel >= 0) ensure_pane(st, hi_rel);
            const i64 accept = accept_of(st);
            i64 vm = e0 % vmod;  // value index, advanced mod-free
            i64 last_ok = st.max_id;  // max SURVIVING id
            i64 folded = 0;
            for (i64 j = 0; j < cnt; ++j) {
                const i64 id = id0 + j;
                const double v = vtab ? vtab[vm]
                                      : (double)vm * vscale + voff;
                const bool dropped = mask && !mask[vm];
                vm += kmod;
                if (vm >= vmod) vm -= vmod;
                if (dropped) continue;  // filtered pre-window
                ++st.arrivals;  // renumber lane: survivors only
                if (id > last_ok) last_ok = id;
                if (id < accept) {
                    ++ignored;
                    continue;
                }
                const i64 nn = hopping ? id / slide : 0;
                if (hopping && id >= nn * slide + win) continue;  // gap
                const i64 p = pane_of(id) - st.pane_base;
                if (p < 0) {
                    ++ignored;
                    continue;
                }
                if (hopping && nn > st.opened_max) st.opened_max = nn;
                fold(st, p, v);
                ++folded;
                if (!is_tb) stamp_last(st, p, id, id);  // the law: ts = id
            }
            folded_singly += folded;
            settle(st, slot, last_ok);
        }
        if (stream_rule) trigger();
    }

    inline i64 n_ready() const { return (i64)(ready.size() - ready_head); }

    // flush()'s note of one key's part in a take: the extent of its
    // taken windows, where its panes and their count prefix were
    // staged, how many windows.  Kept by pool slot beside the key
    // states, not in them: the passes over the taken windows then read
    // 48 bytes a key, and each key state is visited once.
    struct Take {
        i64 stamp = -1, lo = 0, hi = 0, off = 0, pf = 0;
        int32_t n = 0;
    };
    std::vector<Take> takes;

    // A key's windows have been staged: drop the pane prefix nothing
    // will read again -- never past the earliest window still queued in
    // `ready` for the key (a partial take leaves fired-but-unstaged
    // windows, all later than the last one staged, whose extents must
    // stay resident) -- or, where it has nothing opened and nothing
    // queued, note the key as done.
    __attribute__((always_inline)) inline void retire(KeyState& st,
                                                      int32_t slot) {
        if (sparse && st.queued == 0 && st.next_fire > st.opened_max) {
            f_dead.push_back(slot);
            return;
        }
        i64 keep_from = (st.queued > 0 ? st.staged_upto + 1
                                       : st.next_fire) * slide;
        i64 cut = pane_of(keep_from) - st.pane_base;
        if (cut <= 0) return;
        if (cut > st.len) cut = st.len;
        const i64 kept = std::max<i64>(st.room - cut, 0);
        panes_shifted += kept;
        shift(ring(st), cut, kept, -cut);
        stretch(st, st.len - cut, kept);
        st.pane_base += cut;
        if (kind == Kind::ARGMAX) drop_ties(slot, st.pane_base);
    }

    // Stage up to max_windows ready windows as pane partials.
    // Returns the number staged.  A partial take of a long `ready`
    // list costs the windows it takes.
    i64 flush(i64 max_windows) {
        if (kind == Kind::TUPLES) return flush_tuples(max_windows);
        st_n = 0;
        if (n_ready() == 0) return 0;
        const i64 t_in = now_ns(), evict_in = evict_ns;
        const i64 take = std::min<i64>(max_windows, n_ready());
        const Desc* taken = ready.data() + ready_head;
        ++flush_id;
        f_touched.clear();
        if ((i64)takes.size() < n_slots) takes.resize(n_slots);
        // the extent of each key's taken windows (a key's windows were
        // appended in order, but batches interleave keys)
        i64 n_vals = 0;
        for (i64 d = 0; d < take; ++d) {
            const Desc& ds = taken[d];
            Take& t = takes[ds.slot];
            const i64 s = ds.lwid * slide, e = s + win;
            if (t.stamp != flush_id) {
                t.stamp = flush_id;
                t.lo = s;
                t.hi = e;
                t.n = 0;
                f_touched.push_back(ds.slot);
                n_vals += ppw;
            } else {
                if (s < t.lo) { n_vals += (t.lo - s) / pane; t.lo = s; }
                if (e > t.hi) { n_vals += (e - t.hi) / pane; t.hi = e; }
            }
            ++t.n;
        }
        // the buffers only grow: every pane staged is written below, and
        // a resize from empty would fill 8 bytes a pane and lane first
        // (a launch of SG2 stages 7.7 M panes)
        st_n = n_vals;
        grow(st_vals, n_vals);
        if (kind == Kind::MEAN) grow(st_cnts, n_vals);
        // the count prefix tells an empty window from one that holds a
        // tuple and finds a CB window's last tuple: a sparse engine (TB
        // windows on stream time, role SEQ) fires no empty window
        // (fire_key: holds_tuple) and stamps by arithmetic, and keeps none
        const bool prefixed = !sparse;
        if (prefixed) grow(f_prefix, n_vals + (i64)f_touched.size());
        // each key once: its panes staged, its windows accounted, and
        // (TB) its consumed prefix dropped while its state is at hand
        f_dead.clear();
        i64 off = 0, pf = 0;
        for (int32_t slot : f_touched) {
            KeyState& st = state(slot);
            Take& t = takes[slot];
            const i64 p0 = pane_of(t.lo);
            const i64 n_panes = (t.hi - t.lo) / pane;
            t.off = off;
            t.pf = pf;
            // the span in three runs: panes before the ring and behind
            // what it stores hold nothing (no tuples, by construction),
            // the run between is the ring's own and is copied as a block: a
            // window of thousands of panes is staged at the speed of a
            // copy, not of a branch a pane
            const Ring rg = ring(st);
            const i64 r0 = p0 - st.pane_base;
            const i64 b0 = std::min(std::max<i64>(-r0, 0), n_panes);
            const i64 b1 = std::max(b0, std::min(n_panes, st.room - r0));
            double* v = st_vals.data() + off;
            std::fill(v, v + b0, neutral);
            if (b1 > b0)
                std::memcpy(v + b0, rg.acc + r0 + b0,
                            (std::size_t)(b1 - b0) * sizeof(double));
            std::fill(v + b1, v + n_panes, neutral);
            const i64* c = rg.cnt + r0;
            if (kind == Kind::MEAN) {
                double* n = st_cnts.data() + off;
                std::fill(n, n + b0, 0.0);
                for (i64 p = b0; p < b1; ++p) n[p] = (double)c[p];
                std::fill(n + b1, n + n_panes, 0.0);
            }
            if (prefixed) {
                i64* px = f_prefix.data() + pf;
                std::fill(px, px + b0 + 1, (i64)0);
                i64 seen = 0;
                for (i64 p = b0; p < b1; ++p) px[p + 1] = (seen += c[p]);
                std::fill(px + b1 + 1, px + n_panes + 1, seen);
                pf += n_panes + 1;
            }
            off += n_panes;
            st.staged_upto = (t.hi - win) / slide;
            st.queued -= t.n;
            if (is_tb && kind != Kind::ARGMAX) retire(st, slot);
        }
        st_starts.resize(take);
        st_ends.resize(take);
        st_keys.resize(take);
        st_gwids.resize(take);
        st_rts.resize(take);
        for (i64 d = 0; d < take; ++d) {
            const Desc& ds = taken[d];
            const Take& t = takes[ds.slot];
            const i64* pfx = prefixed ? f_prefix.data() + t.pf : nullptr;
            st_keys[d] = ds.key;
            st_gwids[d] = ds.lwid;
            const i64 s = ds.lwid * slide;
            i64 ps = (s - t.lo) / pane;
            i64 pe = ps + ppw;
            // a fired window whose extent holds no tuples (gapped id
            // space) stages an EMPTY pane range (start==end) so the
            // device combine emits the masked neutral 0, exactly like
            // the Python/XLA path (window_compute.py `jnp.where`) --
            // otherwise max/min kinds would emit the +-inf pane fill
            const bool empty = prefixed && pfx[pe] == pfx[ps];
            st_starts[d] = t.off + (empty ? 0 : ps);
            st_ends[d] = t.off + (empty ? 0 : pe);
            if (is_tb) {
                st_rts[d] = s + win - 1;
            } else if (empty) {
                st_rts[d] = 0;
            } else {
                // CB: result ts = ts of the max-id tuple in the extent,
                // which lives in the last non-empty pane of the range
                // (binary search on the span's count prefix)
                const KeyState& st = state(ds.slot);
                i64 q = std::lower_bound(pfx + ps, pfx + pe + 1, pfx[pe])
                    - pfx;
                i64 r = pane_of(t.lo) + (q - 1) - st.pane_base;
                st_rts[d] = (r >= 0 && r < st.room) ? ring(st).lts[r] : 0;
            }
        }
        if (kind == Kind::ARGMAX) stage_argmax(take);
        panes_staged += n_vals;
        windows_staged += take;
        ready_head += take;
        if (ready_head == ready.size()) {
            ready.clear();
            ready_head = 0;
        } else if (ready_head * 2 > ready.size()) {
            ready.erase(ready.begin(), ready.begin() + ready_head);
            ready_head = 0;
        }
        // the CB lane read the rings' stamps just above, ARGMAX their ids
        if (!is_tb || kind == Kind::ARGMAX)
            for (int32_t slot : f_touched) retire(state(slot), slot);
        if (!f_dead.empty()) {
            const i64 t0 = now_ns();
            for (int32_t slot : f_dead) evict(slot);
            evict_ns += now_ns() - t0;
        }
        stage_ns += (now_ns() - t_in) - (evict_ns - evict_in);
        return take;
    }

    void eos() {
        const i64 t0 = now_ns();
        for (int32_t s = 0; s < n_slots; ++s) {
            KeyState& st = state(s);
            if (!st.live) continue;
            fire_key(st, s, INT64_MAX);
            st.indexed = false;
        }
        due.clear();
        trigger_ns += now_ns() - t0;
    }

    void clear() {
        drop_pool();
        ties_of.clear();
        series_of.clear();
        ready.clear();
        ready_head = 0;
        due.clear();
        clear_table(tab.size());
        stream_time = fired_upto = -1;
        keys_opened = keys_evicted = keys_live_peak = windows_fired = 0;
    }

    // -- checkpoint / resume ------------------------------------------
    // Versioned binary snapshot of all mutable state (the live keys'
    // pane rings, the fired-but-unstaged descriptors, the stream time
    // and the churn counters; an evicted key is not in it).  The
    // reference has no checkpointing at all (SURVEY.md §5); this feeds
    // the policy layer in utils/checkpoint.py through the Python
    // state_dict hooks.
    static constexpr i64 SNAP_MAGIC = 0x34'4E'46'57;  // "WFN4"

    template <typename T>
    static void put(std::vector<unsigned char>& b, const T& v) {
        const unsigned char* p = reinterpret_cast<const unsigned char*>(&v);
        b.insert(b.end(), p, p + sizeof(T));
    }
    template <typename T>
    static void put_vec(std::vector<unsigned char>& b,
                        const std::vector<T>& v) {
        put<i64>(b, (i64)v.size());
        const unsigned char* p =
            reinterpret_cast<const unsigned char*>(v.data());
        b.insert(b.end(), p, p + v.size() * sizeof(T));
    }
    // a ring's lane as a vector of `len`: `room` stored, the rest `fill`
    template <typename T>
    static void put_lane(std::vector<unsigned char>& b, const T* lane,
                         i64 room, i64 len, T fill) {
        put<i64>(b, len);
        room = std::min(room, len);
        const unsigned char* p = reinterpret_cast<const unsigned char*>(lane);
        b.insert(b.end(), p, p + room * sizeof(T));
        for (i64 j = room; j < len; ++j) put(b, fill);
    }
    template <typename T>
    static bool get(const unsigned char*& p, const unsigned char* end,
                    T& v) {
        if (p + sizeof(T) > end) return false;
        std::memcpy(&v, p, sizeof(T));
        p += sizeof(T);
        return true;
    }
    template <typename T>
    static bool get_vec(const unsigned char*& p, const unsigned char* end,
                        std::vector<T>& v) {
        i64 n;
        if (!get(p, end, n) || n < 0) return false;
        // division-based check: p + n*sizeof(T) would overflow for a
        // corrupted length field (blob comes from on-disk files)
        if (n > (end - p) / (i64)sizeof(T)) return false;
        v.resize(n);
        std::memcpy(v.data(), p, n * sizeof(T));
        p += n * sizeof(T);
        return true;
    }

    std::vector<unsigned char> serialize() const {
        std::vector<unsigned char> b;
        put(b, SNAP_MAGIC);
        put(b, win); put(b, slide); put(b, delay);
        put(b, (i64)(is_tb ? 1 : 0));
        put(b, (i64)(renumber ? 1 : 0));
        put(b, (i64)(dense ? 1 : 0));
        put(b, (i64)kind);
        put(b, stream_time); put(b, fired_upto);
        put(b, keys_opened); put(b, keys_evicted);
        put(b, keys_live_peak); put(b, windows_fired);
        put(b, n_live);
        for (int32_t s = 0; s < n_slots; ++s) {
            const KeyState& st = state(s);
            if (!st.live) continue;
            put(b, st.key);
            put(b, st.next_fire); put(b, st.anchor);
            put(b, st.opened_max); put(b, st.max_id);
            put(b, st.pane_base); put(b, st.arrivals);
            put(b, st.staged_upto);
            // the ring, a lane after the other, each `len` long: the
            // stored panes and the blank ones behind them
            const Ring r = ring(st);
            put_lane(b, r.acc, st.room, st.len, neutral);
            put_lane(b, r.cnt, st.room, st.len, (i64)0);
            put_lane(b, r.lid, st.room, is_tb ? 0 : st.len, (i64)INT64_MIN);
            put_lane(b, r.lts, st.room, is_tb ? 0 : st.len, (i64)0);
            if (kind == Kind::ARGMAX) {
                // the first id at each pane's max; then the panes that
                // hold more ids at it, each by its place in the ring
                put_lane(b, r.aid, st.room, st.len, (i64)INT64_MIN);
                const auto lo = ties_of.lower_bound({s, INT64_MIN});
                const auto hi = ties_of.lower_bound({s, INT64_MAX});
                put<i64>(b, (i64)std::distance(lo, hi));
                for (auto it = lo; it != hi; ++it) {
                    put<i64>(b, it->first.second - st.pane_base);
                    put_vec(b, it->second);
                }
            }
            if (kind == Kind::TUPLES) put_tuples(b, s);
        }
        put(b, n_ready());
        for (std::size_t i = ready_head; i < ready.size(); ++i) {
            put(b, ready[i].key); put(b, ready[i].lwid);
        }
        return b;
    }

    bool deserialize(const unsigned char* p, i64 len) {
        const unsigned char* end = p + len;
        i64 magic, w, s, d, tb, rn, dn, kd, nk;
        if (!get(p, end, magic) || magic != SNAP_MAGIC) return false;
        if (!get(p, end, w) || !get(p, end, s) || !get(p, end, d)
            || !get(p, end, tb) || !get(p, end, rn) || !get(p, end, dn)
            || !get(p, end, kd))
            return false;
        // snapshot must match this engine's static configuration
        if (w != win || s != slide || d != delay
            || (tb != 0) != is_tb || (rn != 0) != renumber
            || (dn != 0) != dense || kd != (i64)kind)
            return false;
        clear();
        if (!get(p, end, stream_time) || !get(p, end, fired_upto)
            || !get(p, end, keys_opened) || !get(p, end, keys_evicted)
            || !get(p, end, keys_live_peak) || !get(p, end, windows_fired))
            return false;
        if (!get(p, end, nk) || nk < 0) return false;
        const i64 opened = keys_opened, peak = keys_live_peak;
        for (i64 i = 0; i < nk; ++i) {
            i64 key;
            if (!get(p, end, key)) return false;
            bool fresh;
            const int32_t slot = locate(key, fresh).slot;
            if (!fresh) return false;  // a key twice
            KeyState& st = state(slot);
            std::vector<double> acc;
            std::vector<i64> cnt, lid, lts, aid;
            if (!get(p, end, st.next_fire) || !get(p, end, st.anchor)
                || !get(p, end, st.opened_max) || !get(p, end, st.max_id)
                || !get(p, end, st.pane_base) || !get(p, end, st.arrivals)
                || !get(p, end, st.staged_upto)
                || !get_vec(p, end, acc) || !get_vec(p, end, cnt)
                || !get_vec(p, end, lid) || !get_vec(p, end, lts))
                return false;
            if (kind == Kind::ARGMAX
                && (!get_vec(p, end, aid) || aid.size() != acc.size()
                    || !get_ties(p, end, slot, st.pane_base)))
                return false;
            if (cnt.size() != acc.size() || lid.size() != lts.size()
                || acc.size() > (std::size_t)INT32_MAX)
                return false;
            if (kind == Kind::TUPLES && !get_tuples(p, end, slot, cnt))
                return false;
            // CB engines index the id and stamp lanes in lockstep with
            // the accumulators on every ingest; a snapshot with short
            // ts-lane vectors would pass the pairwise checks above and
            // then write out of bounds (a TB engine keeps no such lanes
            // and reads none)
            if (!is_tb && lid.size() != acc.size())
                return false;
            // the ring stays in the key state where what it holds fits
            // (the blank panes behind need no storage)
            const i64 len = (i64)acc.size();
            i64 used = len;
            while (used > 0 && cnt[used - 1] == 0
                   && std::memcmp(&acc[used - 1], &neutral, sizeof(double))
                       == 0
                   && (is_tb || (lid[used - 1] == INT64_MIN
                                 && lts[used - 1] == 0)))
                --used;
            if (used > ring_in()) spill(st, len);
            const i64 room = std::min(len, capacity(st));
            const Ring r = ring(st);
            std::copy(acc.begin(), acc.begin() + room, r.acc);
            std::copy(cnt.begin(), cnt.begin() + room, r.cnt);
            if (!is_tb) {
                std::copy(lid.begin(), lid.begin() + room, r.lid);
                std::copy(lts.begin(), lts.begin() + room, r.lts);
            }
            if (kind == Kind::ARGMAX)
                std::copy(aid.begin(), aid.begin() + room, r.aid);
            st.len = (int32_t)len;
            st.room = (int32_t)room;
            if (stream_rule) index_key(st, slot);
        }
        // locate() counted the restored keys as opened: the snapshot's
        // own counts stand
        keys_opened = opened;
        keys_live_peak = std::max(peak, n_live);
        i64 nr;
        if (!get(p, end, nr) || nr < 0) return false;
        for (i64 i = 0; i < nr; ++i) {
            Desc ds;
            if (!get(p, end, ds.key) || !get(p, end, ds.lwid))
                return false;
            // a queued window belongs to a live key
            std::size_t h = home_of(ds.key);
            const std::size_t mask = tab.size() - 1;
            while (tab[h].slot >= 0 && tab[h].key != ds.key)
                h = (h + 1) & mask;
            if (tab[h].slot < 0) return false;
            ds.slot = tab[h].slot;
            ++state(ds.slot).queued;
            ready.push_back(ds);
        }
        return p == end;
    }

    // THE INSIDE OF A BATCH CALL AND OF A FLUSH, on the clock of open_ns
    // (a call at a time, never a key or a tuple at a time), read by
    // wfn_engine_stats: ingest_walks() from entry to return less what
    // `open` and `trigger` took inside it; of that, the walks that cost
    // by the tuple (gather, note_late, fold_singly) and those that cost
    // by the key (prepare of the keys the call did not open, settle);
    // flush() from entry to return less `evict`.  retire() runs once a
    // key inside the staging loop and has no clock: it counts the ring
    // elements it moves down.
    // Behind every other member and out of line, so that the walks are
    // compiled as they were before there was a clock: members declared
    // among the others move everything behind them (the table, the
    // call's parts) to other offsets and cache lines, and a stamp that
    // is inlined between two walks, or a value that lives across
    // gather()'s loop, takes part in how that loop is laid out (THE
    // TUPLE WALK IS BOUND BY WHAT IT DOES A TUPLE, above; PERF.md
    // section 6, PR 37)
    i64 ingest_ns = 0, tuple_walk_ns = 0, key_walk_ns = 0, stage_ns = 0;
    i64 panes_shifted = 0;
    // a batch call's clock: when it began and when its last piece ended,
    // and `open_ns` / `trigger_ns` as they stood then; when the last
    // call ended (THE TUPLE WALK IN SLICES, in_slices())
    struct CallClock {
        i64 began, mark, open_began, open_mark, trigger_began;
        i64 ended = INT64_MIN / 2;
    } call_clock;
    __attribute__((noinline)) void call_begins() {
        CallClock& c = call_clock;
        c.began = c.mark = now_ns();
        c.open_began = c.open_mark = open_ns;
        c.trigger_began = trigger_ns;
    }
    // the piece of the call that ends here, less the `open` inside it
    __attribute__((noinline)) void piece_ends(i64& into) {
        CallClock& c = call_clock;
        const i64 t = now_ns();
        into += (t - c.mark) - (open_ns - c.open_mark);
        c.mark = t;
        c.open_mark = open_ns;
    }
    __attribute__((noinline)) void call_ends() {
        CallClock& c = call_clock;
        c.ended = now_ns();
        ingest_ns += (c.ended - c.began) - (open_ns - c.open_began)
            - (trigger_ns - c.trigger_began);
    }

    // THE TUPLE WALK IN SLICES (above), behind the clocks for the same
    // reason: the tuples walked by calls split over the lanes, read by
    // wfn_engine_stats; and the rule's test-only override
    // (wfn_engine_lanes_forced): -1 the rule, 0 never, 1 every call on a
    // table that fits the caches, whatever its length and the cores
    i64 walked_in_lanes = 0;
    int lanes_forced = -1;
    const lanes::Hold* lanes_hold = nullptr;  // during a lane-split call
    i64 split_call = -1;      // the call whose slot_of waits in slices
    inline bool in_slices(i64 n) const {
        if (lanes_forced >= 0) return lanes_forced > 0;
        return n >= LANES_FROM && now_ns() - call_clock.ended < LANES_GAP_NS
            && lanes::here() > 1;
    }

    // THE IDS AT A PANE'S MAXIMUM (Kind::ARGMAX; behind every other
    // member for the reason the clocks are, and on walks of its own:
    // no other kind's instantiation has a line of it).  A pane keeps its
    // largest value (the `acc` lane), the smallest id at it (the ring's
    // fifth lane, `aid`) and, where more tuples than one hold it, their
    // ids in id order, equal ids in the order they came (`ties_of`, by
    // pool slot and absolute pane: rare in a law with a long tail, the
    // rule in one of few values).  A tuple's id is its ids column's on CB;
    // on TB, where that column holds the stamps that place the tuple, the
    // caller hands its own id in the stamps' column, which a TB engine
    // reads nowhere else.  A call walks its tuples once as every
    // kind does (gather: how many, the smallest and largest id), then
    // once more for each key's largest value of the call and the ids at
    // it, in arrival order (argmax_of_the_call, split over the lanes
    // where the first walk was: each lane into partials of its own, the
    // caller merging them slice by slice, so the ids come out in the
    // order of one walk whatever the lane count).  A key whose tuples lie
    // in one pane keeps that with one combine (keep_arg), the others
    // fold one by one (fold_arg).  flush() stages the panes' maxima as
    // every kind stages its partials, and beside them each pane's ids
    // (stage_argmax): the device finds each window's maximum and the
    // first pane that holds it, the host the ids of the panes at it.
    // Clocked and counted (wfn_engine_stats): the second walk and the
    // one-by-one fold of an ARGMAX call (argmax_ns, out of what would be
    // tuple_walk_ns), the times a pane's held maximum was set or raised
    // (max_raised), and the ids beyond the first at a staged window's
    // maximum (tie_ids).
    i64 argmax_ns = 0, max_raised = 0, tie_ids = 0;
    struct ArgAt {
        double ext;
        std::vector<i64> ids;         // arrival order until keep_arg sorts
    };
    std::vector<ArgAt> arg_at;        // by the call's index
    std::vector<ArgAt> arg_of_slice[lanes::MAX];
    std::map<std::pair<int32_t, i64>, std::vector<i64>> ties_of;
    std::vector<i64> arg_held, arg_merged;
    // flush()'s: each staged pane's first id at its max (INT64_MIN where
    // it holds none) and the others at it (a prefix over the panes)
    std::vector<i64> st_aid, st_tptr, st_tids;

    static inline void reset_args(std::vector<ArgAt>& a, std::size_t n) {
        if (a.size() < n) a.resize(n);
        for (std::size_t i = 0; i < n; ++i) {
            a[i].ext = -INF;
            a[i].ids.clear();
        }
    }

    template <bool SEL, typename TV>
    static void walk_argmax(ArgAt* at, const int32_t* so, const i64* ids,
                            const TV* vals, Sel s, bool ci, bool cv,
                            i64 from, i64 to) {
        for (i64 j = from; j < to; ++j) {
            const i64 r = SEL ? s.rows[j] : j;
            const double v = (double)vals[cv ? j : r];
            ArgAt& a = at[so[j] & ~LATE_MARK];
            if (!(v >= a.ext)) continue;
            if (v > a.ext) {
                a.ext = v;
                a.ids.clear();
            }
            a.ids.push_back(ids[ci ? j : r]);
        }
    }

    template <bool SEL, typename TV>
    struct ArgSliceWalk {
        Engine* e;
        const i64* ids;
        const TV* vals;
        Sel s;
        bool ci, cv;
        static void walk(void* p, int k) {
            const ArgSliceWalk& w = *static_cast<const ArgSliceWalk*>(p);
            const Slices& ss = slices();
            std::vector<ArgAt>& at = w.e->arg_of_slice[k];
            reset_args(at, ss.of[k].keys.size());
            walk_argmax<SEL>(at.data(), w.e->slot_of.data(), w.ids, w.vals,
                             w.s, w.ci, w.cv, ss.n * k / ss.pieces,
                             ss.n * (k + 1) / ss.pieces);
        }
    };

    // each key's largest value of the call and the ids at it, where the
    // key can fold by key (renumbered ids are implicit: those fold one by
    // one).  `ids` are the tuples' own: on TB the caller's stamp column,
    // which places nothing there and carries them (the ids column holds
    // the stamps that place a tuple in its pane).  Before prepare():
    // slot_of is a split call's slices' own.
    template <bool SEL, bool LANES, typename TV>
    void argmax_of_the_call(const i64* ids, const TV* vals, i64 n, Sel s) {
        reset_args(arg_at, parts.size());
        if (!by_key_lane) return;
        const bool ci = SEL && !(s.through & (is_tb ? SEL_TSS : SEL_IDS));
        const bool cv = SEL && !(s.through & SEL_VALS);
        if (!LANES || split_call != call_id) {
            walk_argmax<SEL>(arg_at.data(), slot_of.data(), ids, vals, s, ci,
                             cv, 0, n);
            return;
        }
        const Slices& ss = slices();
        ArgSliceWalk<SEL, TV> job{this, ids, vals, s, ci, cv};
        lanes_hold->run(&decltype(job)::walk, &job, ss.pieces);
        for (int k = 0; k < ss.pieces; ++k) {
            const Slice& sl = ss.of[k];
            std::vector<ArgAt>& at = arg_of_slice[k];
            for (std::size_t i = 0; i < sl.keys.size(); ++i) {
                ArgAt& a = arg_at[sl.call_of[i]];
                ArgAt& q = at[i];
                if (q.ids.empty() || q.ext < a.ext) continue;
                if (q.ext > a.ext) {
                    a.ext = q.ext;
                    a.ids.swap(q.ids);
                } else {
                    a.ids.insert(a.ids.end(), q.ids.begin(), q.ids.end());
                }
            }
        }
    }

    // ARGMAX's call, on the walks of its own (the three ways a call of
    // any kind runs: ahead of itself, in slices, plainly)
    template <bool SEL, typename TV>
    __attribute__((noinline)) void ingest_argmax(
            const i64* bkeys, const i64* ids, const i64* tss, const TV* vals,
            i64 n, Sel s) {
        if (runs_ahead()) {
            ingest_walks<SEL, true, false, true>(bkeys, ids, tss, vals, n, s);
            return;
        }
        if (ingest_one_key<SEL>(bkeys, ids, tss, vals, n, s)) return;
        if (in_slices(n)) {
            const lanes::Hold hold;
            if (hold.held) {
                lanes_hold = &hold;
                ingest_walks<SEL, false, true, true>(bkeys, ids, tss, vals, n,
                                                     s);
                lanes_hold = nullptr;
                return;
            }
        }
        ingest_walks<SEL, false, false, true>(bkeys, ids, tss, vals, n, s);
    }

    // a pane's ids beyond the first: set, dropped, read
    void set_ties(int32_t slot, i64 pane, const i64* ids, std::size_t n) {
        if (n == 0) {
            if (!ties_of.empty()) ties_of.erase({slot, pane});
            return;
        }
        ties_of[{slot, pane}].assign(ids, ids + n);
    }
    __attribute__((noinline)) void drop_ties(int32_t slot, i64 below) {
        if (ties_of.empty()) return;
        ties_of.erase(ties_of.lower_bound({slot, INT64_MIN}),
                      ties_of.lower_bound({slot, below}));
    }

    // the call's largest value of a key and the ids at it into ring pane
    // `p`: a larger value takes the pane, an equal one adds its ids
    void keep_arg(KeyState& st, int32_t slot, i64 p, ArgAt& a) {
        if (a.ids.empty()) return;
        if (!std::is_sorted(a.ids.begin(), a.ids.end()))
            std::stable_sort(a.ids.begin(), a.ids.end());
        const Ring r = ring(st);
        const i64 pane_at = st.pane_base + p;
        if (a.ext > r.acc[p]) {
            r.acc[p] = a.ext;
            r.aid[p] = a.ids[0];
            set_ties(slot, pane_at, a.ids.data() + 1, a.ids.size() - 1);
            ++max_raised;
            return;
        }
        if (a.ext < r.acc[p]) return;
        arg_held.assign(1, r.aid[p]);
        const auto t = ties_of.find({slot, pane_at});
        if (t != ties_of.end())
            arg_held.insert(arg_held.end(), t->second.begin(),
                            t->second.end());
        arg_merged.resize(arg_held.size() + a.ids.size());
        std::merge(arg_held.begin(), arg_held.end(), a.ids.begin(),
                   a.ids.end(), arg_merged.begin());
        r.aid[p] = arg_merged[0];
        set_ties(slot, pane_at, arg_merged.data() + 1, arg_merged.size() - 1);
    }

    // one tuple into ring pane `p` (its count is the caller's)
    __attribute__((noinline)) void fold_arg(KeyState& st, int32_t slot,
                                            i64 p, double v, i64 id) {
        const Ring r = ring(st);
        ++r.cnt[p];
        if (!(v >= r.acc[p])) return;
        if (v > r.acc[p]) {
            r.acc[p] = v;
            r.aid[p] = id;
            if (!ties_of.empty()) ties_of.erase({slot, st.pane_base + p});
            ++max_raised;
            return;
        }
        std::vector<i64>& t = ties_of[{slot, st.pane_base + p}];
        if (id < r.aid[p]) {
            t.insert(t.begin(), r.aid[p]);
            r.aid[p] = id;
        } else {
            t.insert(std::upper_bound(t.begin(), t.end(), id), id);
        }
    }

    // flush()'s part for ARGMAX, before the rings are retired: each
    // staged pane's first id and the others at its max, and the ids
    // beyond the first at each taken window's maximum (tie_ids)
    __attribute__((noinline)) void stage_argmax(i64 take) {
        grow(st_aid, st_n);
        st_tptr.assign(st_n + 1, 0);
        st_tids.clear();
        for (int32_t slot : f_touched) {
            const KeyState& st = state(slot);
            const Take& t = takes[slot];
            const i64 p0 = pane_of(t.lo);
            const i64 n_panes = (t.hi - t.lo) / pane;
            const i64 r0 = p0 - st.pane_base;
            const i64 b0 = std::min(std::max<i64>(-r0, 0), n_panes);
            const i64 b1 = std::max(b0, std::min(n_panes, st.room - r0));
            i64* a = st_aid.data() + t.off;
            std::fill(a, a + b0, INT64_MIN);
            if (b1 > b0)
                std::memcpy(a + b0, ring(st).aid + r0 + b0,
                            (std::size_t)(b1 - b0) * sizeof(i64));
            std::fill(a + b1, a + n_panes, INT64_MIN);
            const auto hi = ties_of.lower_bound({slot, p0 + b1});
            for (auto it = ties_of.lower_bound({slot, p0 + b0}); it != hi;
                 ++it) {
                st_tptr[t.off + (it->first.second - p0) + 1] =
                    (i64)it->second.size();
                st_tids.insert(st_tids.end(), it->second.begin(),
                               it->second.end());
            }
        }
        for (i64 q = 0; q < st_n; ++q) st_tptr[q + 1] += st_tptr[q];
        for (i64 d = 0; d < take; ++d) {
            const i64 lo = st_starts[d], hi = st_ends[d];
            if (hi <= lo) continue;
            const double m = *std::max_element(st_vals.data() + lo,
                                               st_vals.data() + hi);
            i64 held = 0;
            for (i64 q = lo; q < hi; ++q)
                if (st_vals[q] == m)
                    held += 1 + st_tptr[q + 1] - st_tptr[q];
            tie_ids += held - 1;
        }
    }

    // deserialize()'s: a key's panes that hold more ids than one
    bool get_ties(const unsigned char*& p, const unsigned char* end,
                  int32_t slot, i64 base) {
        i64 nt;
        if (!get(p, end, nt) || nt < 0) return false;
        for (i64 i = 0; i < nt; ++i) {
            i64 rel;
            std::vector<i64> ids;
            if (!get(p, end, rel) || !get_vec(p, end, ids)) return false;
            if (!ids.empty()) ties_of[{slot, base + rel}] = std::move(ids);
        }
        return true;
    }

    // THE ONE-KEY WALK (behind every other member for the reason the
    // clocks are).  A call whose tuples all carry one key (a window with
    // no key: WindFlow's non-keyed Win_Seq, Beam's Combine.globally)
    // needs none of what gather() does a tuple for many keys: no probe,
    // no slot_of, no partial in memory that every tuple of the call
    // updates in turn (one chain of dependent updates: one key walked
    // slower than 111, PERF.md section 6).  One pass keeps the
    // key's partial in registers -- how many, the smallest and largest
    // id, for MAX / MIN the extreme, for ARGMAX the largest value and the
    // ids at it in arrival order (argmax_of_the_call's walk, in the same
    // pass) -- and note_late()'s test whether the ids run in order from
    // the stream time on.  It then leaves what the general walks leave:
    // the key through dense_of() (opened where it has no record), parts[0]
    // and arg_at[0]; slot_of only where a later step reads it (a call
    // with late tuples, which note_late() marks; a key that folds one by
    // one), so prepare(), keep_arg(), settle(), trigger() and flush() run
    // as after the general walks, and a call that straddles a pane edge
    // folds one by one as it would.
    // No knob: what decides is what the engine observes.  A lane that
    // folds by key (by_key_lane), a table that does not run ahead, and
    // the engine's own last call touched one key (parts, as that call
    // left them).  The pass compares each tuple's key with the first's;
    // at a block of tuples that holds another key it gives up, having
    // written nothing the general walk reads, and the call takes the
    // general walk from the start: one block where the guess fails.
    // On the caller alone, whatever the call's length: the pass is bound
    // by the columns it reads, not by what it does a tuple, and on the
    // chip's host it read a tuple in 1.0-1.1 ns on one core against
    // 1.7 ns split over four lanes with their wake and merge (THE TUPLE
    // WALK IN SLICES; PERF.md section 6).
    // Counted (wfn_engine_stats): walked_one_key, the tuples of the calls
    // it took.  Its time is the call's tuple walk (tuple_walk_ns);
    // argmax_ns keeps what is still apart, the one-by-one fold.
    i64 walked_one_key = 0;
    i64 slots_owed = -1;      // the call whose slot_of it left unwritten:
                              // 0 a tuple (wfn_engine_last_call's)

    // The pass, over the kinds as walk_tuples() is over EXT (0 reads no
    // value, 1 the largest, 2 the smallest; ARG the largest and the ids
    // at it, into arg_at[0]).  False where a block of tuples holds one of
    // another key.  A block of ONE_KEY_BLOCK is walked with no branch a
    // tuple (the compilers make it a vector loop): the keys against the
    // first, the smallest and largest id, whether the ids run in order,
    // and whether a value of the block moves the extreme held; only a
    // block that holds such a value is walked again, tuple by tuple, in
    // arrival order (a few blocks of a call: the extreme of a stream's
    // values is seldom passed).
    static constexpr i64 ONE_KEY_BLOCK = 256;
    template <int EXT, bool ARG, bool SEL, typename TV>
    __attribute__((always_inline)) inline bool one_key_pass(
            const i64* bkeys, const i64* ids, const i64* tss, const TV* vals,
            i64 n, Sel s, i64 key, Part& pt, bool& in_order) {
        const bool ck = SEL && !(s.through & SEL_KEYS);
        const bool ci = SEL && !(s.through & SEL_IDS);
        const bool ct = SEL && !(s.through & SEL_TSS);
        const bool cv = SEL && !(s.through & SEL_VALS);
        auto id_at = [&](i64 j) { return ids[ci || !SEL ? j : s.rows[j]]; };
        auto v_at = [&](i64 j) {
            return (double)vals[cv || !SEL ? j : s.rows[j]];
        };
        i64 lo = INT64_MAX, hi = INT64_MIN, back = id_at(0) < stream_time;
        double ext = neutral, top = -INF;
        std::vector<i64>* at_top = ARG ? &arg_at[0].ids : nullptr;
        for (i64 b = 0; b < n; b += ONE_KEY_BLOCK) {
            const i64 e = std::min<i64>(b + ONE_KEY_BLOCK, n);
            i64 other = 0;
            for (i64 j = b; j < e; ++j) {
                other |= bkeys[ck || !SEL ? j : s.rows[j]] ^ key;
                const i64 id = id_at(j);
                lo = id < lo ? id : lo;
                hi = id > hi ? id : hi;
            }
            if (other != 0) return false;
            for (i64 j = b > 0 ? b : 1; j < e; ++j)
                back += id_at(j) < id_at(j - 1);
            if (EXT == 0 && !ARG) continue;
            const double held = ARG ? top : ext;
            i64 moves = 0;
            for (i64 j = b; j < e; ++j) {
                const double v = v_at(j);
                moves += EXT == 1 ? v > held : EXT == 2 ? v < held
                                                        : v >= held;
            }
            if (moves == 0) continue;
            for (i64 j = b; j < e; ++j) {
                const double v = v_at(j);
                if (EXT == 1 && v > ext) ext = v;
                if (EXT == 2 && v < ext) ext = v;
                if (ARG && v >= top) {
                    if (v > top) {
                        top = v;
                        at_top->clear();
                    }
                    at_top->push_back(tss[ct || !SEL ? j : s.rows[j]]);
                }
            }
        }
        pt = Part{n, lo, hi, ext};
        if (ARG) arg_at[0].ext = top;
        in_order = back == 0;
        return true;
    }

    // slot_of as gather() leaves it on one key: 0 a tuple
    inline void one_key_slots(i64 n) {
        std::fill(slot_of.begin(), slot_of.begin() + n, 0);
    }

    // The call after the pass, ingest_walks()'s for one key, in pieces
    // out of line: each function the general walks share gains one caller
    // and no more, so that the compiler keeps the out-of-line functions
    // it made before there was this walk (docs/RUNTIME.md 5b)
    template <int EXT, bool ARG, bool SEL, typename TV>
    bool one_key_call(const i64* bkeys, const i64* ids, const i64* tss,
                      const TV* vals, i64 n, Sel s) {
        call_begins();
        if (ARG) reset_args(arg_at, parts.size());  // 1: the rule's
        const i64 key = bkeys[SEL && (s.through & SEL_KEYS) ? s.rows[0] : 0];
        Part pt;
        bool in_order;
        if (!one_key_pass<EXT, ARG, SEL>(bkeys, ids, tss, vals, n, s, key, pt,
                                         in_order))
            return false;
        one_key_open(key, pt, n);
        if (!in_order)
            one_key_late(ids, SEL && (s.through & SEL_IDS) ? s.rows : nullptr,
                         n);
        piece_ends(tuple_walk_ns);
        const bool singly = one_key_prepare(ARG, n);
        if (singly) {
            if (in_order) one_key_slots(n);
            fold_singly<SEL, ARG>(ids, tss, vals, n, s);
        }
        one_key_settle(ARG ? argmax_ns : tuple_walk_ns, !singly && in_order);
        return true;
    }

    // the key of the call and its partial where gather() leaves them
    __attribute__((noinline)) void one_key_open(i64 key, const Part& pt,
                                                i64 n) {
        ++call_id;
        parts.clear();
        d_slot.clear();
        d_state.clear();
        opened_now.clear();
        if ((i64)slot_of.size() < n) slot_of.resize(n);
        dense_of(key);
        parts[0] = pt;
        walked_one_key += n;
    }

    // a call whose ids do not run in order: its late tuples marked
    __attribute__((noinline)) void one_key_late(const i64* ids,
                                                const i64* rows, i64 n) {
        one_key_slots(n);
        if (rows != nullptr)
            note_late<true>(ids, rows, n);
        else
            note_late<false>(ids, nullptr, n);
    }

    // the visit by key; true where the key folds one by one
    __attribute__((noinline)) bool one_key_prepare(bool arg, i64 n) {
        d_accept.resize(parts.size());
        d_single.resize(parts.size());
        n_single = 0;
        ++key_touches;
        const i64 t0 = opened_now.empty() ? 0 : now_ns();
        if (arg)
            prepare<true>(0);
        else
            prepare<false>(0);
        if (!opened_now.empty()) open_ns += now_ns() - t0;
        probes_ahead = (i64)(1 - opened_now.size()) * AHEAD_BLOCK > n;
        piece_ends(key_walk_ns);
        return n_single != 0;
    }

    // settle() and the firing; `piece`: the clock of what ran since the
    // visit by key
    __attribute__((noinline)) void one_key_settle(i64& piece, bool owed) {
        piece_ends(piece);
        settle(*d_state[0], d_slot[0], parts[0].hi);
        piece_ends(key_walk_ns);
        trigger();            // a lane that folds by key has the stream rule
        call_ends();
        slots_owed = owed ? call_id : -1;
    }

    // The one way in, from ingest_batch() and ingest_argmax() where the
    // table does not run ahead: true where the call was taken whole
    template <bool SEL, typename TV>
    __attribute__((noinline)) bool ingest_one_key(
            const i64* bkeys, const i64* ids, const i64* tss, const TV* vals,
            i64 n, Sel s) {
        if (!by_key_lane || parts.size() != 1 || n <= 0) return false;
        switch (kind) {
            case Kind::COUNT:
                return one_key_call<0, false, SEL>(bkeys, ids, tss, vals, n,
                                                   s);
            case Kind::MAX:
                return one_key_call<1, false, SEL>(bkeys, ids, tss, vals, n,
                                                   s);
            case Kind::MIN:
                return one_key_call<2, false, SEL>(bkeys, ids, tss, vals, n,
                                                   s);
            default:          // ARGMAX
                return one_key_call<0, true, SEL>(bkeys, ids, tss, vals, n,
                                                  s);
        }
    }

    // A PANE THAT KEEPS ITS TUPLES (Kind::TUPLES; behind every other
    // member for the reason the clocks are, and on walks of its own: no
    // other kind's instantiation has a line of it).  What a user's window
    // function reads is every tuple of the window, and no partial gives
    // that (a median, a percentile, an order-sensitive sum).  So the
    // ring keeps what it keeps for every kind -- a count a pane, which
    // fires, anchors, accepts, evicts and retires as it does for COUNT
    // -- and beside it each key's SERIES: the values of its tuples from
    // the ring's first pane on, in id order, those of equal id in the
    // order they came (an insert where a straggler comes within the
    // delay; an append for a tuple in order).  The counts are the index:
    // pane p's tuples are the series' from the sum of the counts before
    // p on, so a fired window's extent is a difference of two count
    // prefixes.  A call walks its tuples as COUNT's does (gather, in an
    // instantiation of its own) and keeps each one by one (fold_singly,
    // keep_tuple).  flush_tuples() stages a key's span once, as a block
    // copy of its series, with every taken window's extent into it: the
    // order the Python store gives (operators/tpu/window_store.py).
    // The panes retire() cuts take their tuples with them; a slot's next
    // key starts with an empty series.
    // Counted (wfn_engine_stats): tuples_staged, the tuples the flushes
    // copied into launch buffers (the panes of the spans are
    // panes_staged, as for every kind).
    struct Series {
        std::vector<i64> ids;
        std::vector<double> vals;
        std::size_t head = 0;     // the tuples before it are retired
    };
    std::vector<Series> series_of;    // by pool slot
    i64 tuples_staged = 0;

    // a series for every pool slot; the keys the last call opened start
    // theirs empty (a slot's former key left its tuples there: evict()
    // is every kind's and keeps no lane of this one)
    __attribute__((noinline)) void fresh_series() {
        if (series_of.size() < (std::size_t)n_slots)
            series_of.resize(n_slots);
        for (int32_t d : opened_now) drop_tuples(d_slot[d], INT64_MAX);
    }

    // one tuple into ring pane `p` of the key in `slot`
    __attribute__((noinline)) void keep_tuple(KeyState& st, int32_t slot,
                                              i64 p, double v, i64 id) {
        ++ring(st).cnt[p];
        Series& s = series_of[slot];
        if (s.ids.size() == s.head || id >= s.ids.back()) {
            s.ids.push_back(id);
            s.vals.push_back(v);
            return;
        }
        const auto at = std::upper_bound(s.ids.begin() + s.head,
                                         s.ids.end(), id);
        s.vals.insert(s.vals.begin() + (at - s.ids.begin()), v);
        s.ids.insert(at, id);
    }

    // the tuples of the ring's first `panes` panes
    inline i64 tuples_before(const KeyState& st, i64 panes) const {
        const i64* c = ring(st).cnt;
        i64 n = 0;
        for (i64 p = 0, e = std::min<i64>(panes, st.room); p < e; ++p)
            n += c[p];
        return n;
    }

    // the first `n` tuples of the key's series retired (INT64_MAX: all)
    __attribute__((noinline)) void drop_tuples(int32_t slot, i64 n) {
        if ((std::size_t)slot >= series_of.size()) return;
        Series& s = series_of[slot];
        if (n >= (i64)(s.ids.size() - s.head)) {  // all: the storage stays
            s.ids.clear();
            s.vals.clear();
            s.head = 0;
            return;
        }
        s.head += n;
        // the retired prefix goes once it is half the series
        if (s.head < 64 || s.head * 2 < s.ids.size()) return;
        s.ids.erase(s.ids.begin(), s.ids.begin() + s.head);
        s.vals.erase(s.vals.begin(), s.vals.begin() + s.head);
        s.head = 0;
    }

    // A TUPLES engine's call, on the walks of its own (ahead of itself,
    // or plainly: no lanes, the keep is one by one)
    template <bool SEL, typename TV>
    __attribute__((noinline)) void ingest_tuples(
            const i64* bkeys, const i64* ids, const i64* tss, const TV* vals,
            i64 n, Sel s) {
        if (runs_ahead())
            ingest_walks<SEL, true, false, false, true>(bkeys, ids, tss, vals,
                                                        n, s);
        else
            ingest_walks<SEL, false, false, false, true>(bkeys, ids, tss,
                                                         vals, n, s);
    }

    // flush() for TUPLES: the spans as flush() takes them, each key's
    // count prefix over its span, its tuples as one block copy of its
    // series, and every window's extent the difference of two prefixes
    __attribute__((noinline)) i64 flush_tuples(i64 max_windows) {
        st_n = 0;
        if (n_ready() == 0) return 0;
        const i64 t_in = now_ns(), evict_in = evict_ns;
        const i64 take = std::min<i64>(max_windows, n_ready());
        const Desc* taken = ready.data() + ready_head;
        ++flush_id;
        f_touched.clear();
        if ((i64)takes.size() < n_slots) takes.resize(n_slots);
        // each key's span over its taken windows, as flush() takes it
        i64 n_panes = 0;
        for (i64 d = 0; d < take; ++d) {
            const Desc& ds = taken[d];
            Take& t = takes[ds.slot];
            const i64 s = ds.lwid * slide, e = s + win;
            if (t.stamp != flush_id) {
                t.stamp = flush_id;
                t.lo = s;
                t.hi = e;
                t.n = 0;
                f_touched.push_back(ds.slot);
                n_panes += ppw;
            } else {
                if (s < t.lo) { n_panes += (t.lo - s) / pane; t.lo = s; }
                if (e > t.hi) { n_panes += (e - t.hi) / pane; t.hi = e; }
            }
            ++t.n;
        }
        grow(f_prefix, n_panes + (i64)f_touched.size());
        // each key's count prefix over its span, and where its tuples go
        i64 n_vals = 0, pf = 0;
        for (int32_t slot : f_touched) {
            const KeyState& st = state(slot);
            Take& t = takes[slot];
            const i64 np = (t.hi - t.lo) / pane;
            const i64 r0 = pane_of(t.lo) - st.pane_base;
            const i64 b0 = std::min(std::max<i64>(-r0, 0), np);
            const i64 b1 = std::max(b0, std::min(np, st.room - r0));
            const i64* c = ring(st).cnt + r0;
            i64* px = f_prefix.data() + pf;
            std::fill(px, px + b0 + 1, (i64)0);
            i64 seen = 0;
            for (i64 p = b0; p < b1; ++p) px[p + 1] = (seen += c[p]);
            std::fill(px + b1 + 1, px + np + 1, seen);
            t.off = n_vals;
            t.pf = pf;
            n_vals += seen;
            pf += np + 1;
        }
        st_n = n_vals;
        grow(st_vals, n_vals);
        // the tuples, a key's span as one block of its series
        for (int32_t slot : f_touched) {
            KeyState& st = state(slot);
            const Take& t = takes[slot];
            const i64 n = f_prefix[t.pf + (t.hi - t.lo) / pane];
            if (n > 0) {
                const i64 r0 = pane_of(t.lo) - st.pane_base;
                const Series& s = series_of[slot];
                const i64 from = (i64)s.head
                    + tuples_before(st, std::max<i64>(r0, 0));
                std::memcpy(st_vals.data() + t.off, s.vals.data() + from,
                            (std::size_t)n * sizeof(double));
            }
            st.staged_upto = (t.hi - win) / slide;
            st.queued -= t.n;
        }
        st_starts.resize(take);
        st_ends.resize(take);
        st_keys.resize(take);
        st_gwids.resize(take);
        st_rts.resize(take);
        for (i64 d = 0; d < take; ++d) {
            const Desc& ds = taken[d];
            const Take& t = takes[ds.slot];
            const i64* pfx = f_prefix.data() + t.pf;
            st_keys[d] = ds.key;
            st_gwids[d] = ds.lwid;
            const i64 s = ds.lwid * slide;
            const i64 ps = (s - t.lo) / pane, pe = ps + ppw;
            st_starts[d] = t.off + pfx[ps];
            st_ends[d] = t.off + pfx[pe];
            if (is_tb) {
                st_rts[d] = s + win - 1;
            } else if (pfx[pe] == pfx[ps]) {
                st_rts[d] = 0;
            } else {
                // CB: the stamp of the window's last tuple (flush())
                const KeyState& st = state(ds.slot);
                const i64 q = std::lower_bound(pfx + ps, pfx + pe + 1,
                                               pfx[pe]) - pfx;
                const i64 r = pane_of(t.lo) + (q - 1) - st.pane_base;
                st_rts[d] = (r >= 0 && r < st.room) ? ring(st).lts[r] : 0;
            }
        }
        panes_staged += n_panes;
        windows_staged += take;
        tuples_staged += n_vals;
        ready_head += take;
        if (ready_head == ready.size()) {
            ready.clear();
            ready_head = 0;
        } else if (ready_head * 2 > ready.size()) {
            ready.erase(ready.begin(), ready.begin() + ready_head);
            ready_head = 0;
        }
        // retire() cuts the panes no window still to be staged reads
        // (its rule, here first for the tuples of those panes); a key it
        // finds done is evicted, and its series is emptied when its
        // slot next holds a key (fresh_series)
        f_dead.clear();
        for (int32_t slot : f_touched) {
            KeyState& st = state(slot);
            const i64 cut = pane_of((st.queued > 0 ? st.staged_upto + 1
                                                   : st.next_fire) * slide)
                - st.pane_base;
            if (cut > 0) drop_tuples(slot, tuples_before(st, cut));
            retire(st, slot);
        }
        if (!f_dead.empty()) {
            const i64 t0 = now_ns();
            for (int32_t slot : f_dead) evict(slot);
            evict_ns += now_ns() - t0;
        }
        stage_ns += (now_ns() - t_in) - (evict_ns - evict_in);
        return take;
    }

    // serialize()'s: the key's tuples, ids and values, in series order
    void put_tuples(std::vector<unsigned char>& b, int32_t slot) const {
        static const Series none;
        const Series& s = (std::size_t)slot < series_of.size()
            ? series_of[slot] : none;
        const i64 n = (i64)(s.ids.size() - s.head);
        put<i64>(b, n);
        const auto* pi = reinterpret_cast<const unsigned char*>(
            s.ids.data() + s.head);
        b.insert(b.end(), pi, pi + n * sizeof(i64));
        put<i64>(b, n);
        const auto* pv = reinterpret_cast<const unsigned char*>(
            s.vals.data() + s.head);
        b.insert(b.end(), pv, pv + n * sizeof(double));
    }

    // deserialize()'s: as many tuples as the ring's counts, in id order
    bool get_tuples(const unsigned char*& p, const unsigned char* end,
                    int32_t slot, const std::vector<i64>& cnt) {
        if (series_of.size() < (std::size_t)n_slots)
            series_of.resize(n_slots);
        Series& s = series_of[slot];
        if (!get_vec(p, end, s.ids) || !get_vec(p, end, s.vals))
            return false;
        s.head = 0;
        i64 held = 0;
        for (i64 c : cnt) held += c;
        return s.ids.size() == s.vals.size() && (i64)s.ids.size() == held
            && std::is_sorted(s.ids.begin(), s.ids.end());
    }
};

}  // namespace

extern "C" {

void* wfn_engine_new(i64 win, i64 slide, int is_tb, i64 delay,
                     int renumber, int kind, int dense) {
    return new Engine(win, slide, is_tb != 0, delay, renumber != 0,
                      static_cast<Kind>(kind), dense != 0);
}

void wfn_engine_free(void* e) { delete static_cast<Engine*>(e); }

// Ingest a columnar batch (keys need not be grouped); returns the
// number of ready (fired, unstaged) windows afterwards.
i64 wfn_engine_ingest(void* ep, const i64* keys, const i64* ids,
                      const i64* tss, const double* vals, i64 n) {
    Engine& e = *static_cast<Engine*>(ep);
    e.ingest_batch<false>(keys, ids, tss, vals, n);
    return e.n_ready();
}

// f32 value column variant (no widening copy on the host side).
i64 wfn_engine_ingest_f32(void* ep, const i64* keys, const i64* ids,
                          const i64* tss, const float* vals, i64 n) {
    Engine& e = *static_cast<Engine*>(ep);
    e.ingest_batch<false>(keys, ids, tss, vals, n);
    return e.n_ready();
}

// The same through a selection (Engine::Sel): the call's n rows are
// rows sel[0..n) of the columns named in `through`, each n_base long,
// and rows 0..n of the others.  The rows are checked before a tuple is
// folded (one pass, no branch a row): -1 and nothing ingested where one
// lies outside [0, n_base).
static bool rows_in_range(const i64* sel, i64 n, i64 n_base) {
    unsigned bad = 0;
    for (i64 j = 0; j < n; ++j)
        bad |= (std::uint64_t)sel[j] >= (std::uint64_t)n_base;
    return !bad;
}

i64 wfn_engine_ingest_sel(void* ep, const i64* keys, const i64* ids,
                          const i64* tss, const double* vals,
                          const i64* sel, i64 n, int through, i64 n_base) {
    if (!rows_in_range(sel, n, n_base)) return -1;
    Engine& e = *static_cast<Engine*>(ep);
    e.ingest_batch<true>(keys, ids, tss, vals, n, Engine::Sel{sel, through});
    return e.n_ready();
}

i64 wfn_engine_ingest_sel_f32(void* ep, const i64* keys, const i64* ids,
                              const i64* tss, const float* vals,
                              const i64* sel, i64 n, int through,
                              i64 n_base) {
    if (!rows_in_range(sel, n, n_base)) return -1;
    Engine& e = *static_cast<Engine*>(ep);
    e.ingest_batch<true>(keys, ids, tss, vals, n, Engine::Sel{sel, through});
    return e.n_ready();
}

// Fused synthesis + ingest of the declared synthetic law; returns the
// number of ready (fired, unstaged) windows afterwards.
i64 wfn_engine_synth_ingest(void* ep, i64 start, i64 n, i64 n_keys,
                            i64 vmod, double vscale, double voff) {
    Engine& e = *static_cast<Engine*>(ep);
    e.synth_ingest(start, n, n_keys, vmod, vscale, voff);
    return e.n_ready();
}

// Masked/tabled variant: mask is uint8[vmod] (entry 0 drops the event
// before the window op -- the folded form of a declared value-predicate
// Filter); vtab is an optional double[vmod] per-residue value table
// (sequentially-applied map chain).  Either may be null.
i64 wfn_engine_synth_ingest_masked(void* ep, i64 start, i64 n,
                                   i64 n_keys, i64 vmod, double vscale,
                                   double voff,
                                   const unsigned char* mask,
                                   const double* vtab) {
    Engine& e = *static_cast<Engine*>(ep);
    e.synth_ingest(start, n, n_keys, vmod, vscale, voff, mask, vtab);
    return e.n_ready();
}

i64 wfn_engine_ready(void* ep) {
    return static_cast<Engine*>(ep)->n_ready();
}

i64 wfn_engine_ignored(void* ep) {
    return static_cast<Engine*>(ep)->ignored;
}

// What key churn costs, how many keys there are, how the fold went and
// what disorder it met, into out[25].  The first nineteen: nanoseconds
// spent creating key
// states and moving anchors back (open), finding and queueing fired
// windows (trigger) and evicting (evict), since the engine was made; keys
// opened, keys evicted, keys live now and at their peak, windows fired;
// tuples folded with their key's others of the call in one combine,
// tuples folded one by one; tuples accepted whose stamp lay behind the
// stream time when they came, times a live key's anchor moved back,
// tuples ignored; the stream time (-1 before the first stamp); the keys
// the per-key visit of the calls met, those of them in a call that ran
// ahead of itself (Engine, "WHERE A CALL RUNS AHEAD OF ITSELF"), the
// rings that left their key state (KeyState), and the pane partials and
// windows flush() staged.  out[19]: the tuples of calls whose walk was
// split over the host lanes (Engine, "THE TUPLE WALK IN SLICES").
// Behind those twenty, into out[20..25): what
// a batch call took less its `open` and `trigger` (ingest), of that the
// walks by the tuple and the walks by the key, what flush() took less
// its `evict` (stage), nanoseconds on the same clock, and the ring
// elements retire() moved down (Engine, beside open_ns).  out[25] is the
// caller's (runtime/native.py keeps its own clock there).  Into
// out[26..29): an ARGMAX engine's clock of its own walks, the times a
// pane's held maximum was set or raised, and the ids beyond the first at
// the staged windows' maxima (Engine, "THE IDS AT A PANE'S MAXIMUM").
// out[29]: the tuples of the calls the one-key walk took (Engine, "THE
// ONE-KEY WALK").  out[30]: the tuples a TUPLES engine's flushes copied
// into launch buffers (Engine, "A PANE THAT KEEPS ITS TUPLES").
void wfn_engine_stats(void* ep, i64* out) {
    const Engine& e = *static_cast<Engine*>(ep);
    out[0] = e.open_ns;
    out[1] = e.trigger_ns;
    out[2] = e.evict_ns;
    out[3] = e.keys_opened;
    out[4] = e.keys_evicted;
    out[5] = e.n_live;
    out[6] = e.keys_live_peak;
    out[7] = e.windows_fired;
    out[8] = e.folded_by_key;
    out[9] = e.folded_singly;
    out[10] = e.late_seen - e.late_dropped;
    out[11] = e.anchors_moved;
    out[12] = e.ignored;
    out[13] = e.stream_time;
    out[14] = e.key_touches;
    out[15] = e.walked_ahead;
    out[16] = e.rings_spilled;
    out[17] = e.panes_staged;
    out[18] = e.windows_staged;
    out[19] = e.walked_in_lanes;
    out[20] = e.ingest_ns;
    out[21] = e.tuple_walk_ns;
    out[22] = e.key_walk_ns;
    out[23] = e.stage_ns;
    out[24] = e.panes_shifted;
    out[26] = e.argmax_ns;
    out[27] = e.max_raised;
    out[28] = e.tie_ids;
    out[29] = e.walked_one_key;
    out[30] = e.tuples_staged;
}

// An ARGMAX engine's last flush, beside its pane maxima: each staged
// pane's first id at its max (INT64_MIN where it holds none), the prefix
// over the panes of the other ids at it, and those ids.  Valid until the
// next flush; 0 ids and nothing written on any other kind.
i64 wfn_engine_argmax_staged(void* ep, i64** first, i64** tie_ptr,
                             i64** tie_ids) {
    Engine& e = *static_cast<Engine*>(ep);
    if (e.kind != Kind::ARGMAX) return 0;
    *first = e.st_aid.data();
    *tie_ptr = e.st_tptr.data();
    *tie_ids = e.st_tids.data();
    return (i64)e.st_tids.size();
}

// TEST ONLY (tests/test_native_runtime.py), no part of the engine's
// configuration: the rule that splits a call's tuple walk over the
// host lanes (Engine, "THE TUPLE WALK IN SLICES") held to never (0) or
// to every call whose table fits the caches (1), or left to decide (-1)
void wfn_engine_lanes_forced(void* ep, int mode) {
    static_cast<Engine*>(ep)->lanes_forced = mode < 0 ? -1 : mode > 0;
}

// TEST ONLY: what the last batch call, of `n` tuples, left in the
// per-call arrays, into out: the keys it met; per key in the order of
// first touch its key, count, smallest and largest id, the extreme's
// bits and its pool slot; slot_of[0..n) (made the call's here where a
// lane-split call left it in its slices' indices, or where the one-key
// walk left it unwritten); the keys it opened and their indices.
// Returns the length, minus the length where `cap` is less, 0 where
// another engine's call has had the slices since.
i64 wfn_engine_last_call(void* ep, i64 n, i64* out, i64 cap) {
    Engine& e = *static_cast<Engine*>(ep);
    const i64 nd = (i64)e.parts.size(), no = (i64)e.opened_now.size();
    const i64 len = 1 + 6 * nd + n + 1 + no;
    if (cap < len || n > (i64)e.slot_of.size()) return -len;
    if (!e.slots_of_the_call()) return 0;
    if (e.slots_owed == e.call_id) {
        e.one_key_slots(n);
        e.slots_owed = -1;
    }
    i64* o = out;
    *o++ = nd;
    for (i64 d = 0; d < nd; ++d) {
        const auto& pt = e.parts[d];
        *o++ = e.d_state[d]->key;
        *o++ = pt.count;
        *o++ = pt.lo;
        *o++ = pt.hi;
        std::memcpy(o++, &pt.ext, sizeof(i64));
        *o++ = e.d_slot[d];
    }
    for (i64 j = 0; j < n; ++j) *o++ = e.slot_of[j];
    *o++ = no;
    for (i64 i = 0; i < no; ++i) *o++ = e.opened_now[i];
    return len;
}

// TEST ONLY: this process's lanes, into out[4]: the helper threads its
// pool has (0 before the first call that engages them, and in a forked
// child until its own first), their CPU time in nanoseconds, the lanes
// the rule gives this process, and the shortest call it splits
void wfn_lanes_info(i64* out) {
    const lanes::Pool* p = lanes::the_pool.load();
    const bool mine = p != nullptr && p->pid == getpid();
    out[0] = mine ? (i64)p->helpers.size() : 0;
    out[1] = mine ? p->cpu_ns() : 0;
    out[2] = lanes::here();
    out[3] = Engine::LANES_FROM;
}

void wfn_engine_eos(void* ep) { static_cast<Engine*>(ep)->eos(); }

// Stage up to max_windows; returns B staged.  Pointers are valid until
// the next flush call.  `cnts` carries per-pane tuple counts (same
// layout as vals) for the MEAN kind and is empty otherwise.
i64 wfn_engine_flush(void* ep, i64 max_windows, double** vals, i64* n_vals,
                     double** cnts, i64* n_cnts,
                     i64** starts, i64** ends, i64** keys, i64** gwids,
                     i64** rts) {
    Engine& e = *static_cast<Engine*>(ep);
    i64 b = e.flush(max_windows);
    *vals = e.st_vals.data();
    *n_vals = e.st_n;
    *cnts = e.st_cnts.data();
    *n_cnts = e.kind == Kind::MEAN ? e.st_n : 0;
    *starts = e.st_starts.data();
    *ends = e.st_ends.data();
    *keys = e.st_keys.data();
    *gwids = e.st_gwids.data();
    *rts = e.st_rts.data();
    return b;
}

// Snapshot the engine's mutable state.  First call with buf=nullptr to
// get the size; second call fills the caller's buffer.  Returns the
// blob size, or -1 when the provided buffer is too small.
i64 wfn_engine_serialize(void* ep, unsigned char* buf, i64 cap) {
    Engine& e = *static_cast<Engine*>(ep);
    std::vector<unsigned char> b = e.serialize();
    if (buf == nullptr) return (i64)b.size();
    if (cap < (i64)b.size()) return -1;
    std::memcpy(buf, b.data(), b.size());
    return (i64)b.size();
}

// Restore a snapshot; returns 1 on success, 0 on a malformed blob or a
// configuration mismatch (the engine is left cleared in that case).
int wfn_engine_deserialize(void* ep, const unsigned char* buf, i64 len) {
    Engine& e = *static_cast<Engine*>(ep);
    bool ok = e.deserialize(buf, len);
    if (!ok) e.clear();  // never leave partially-restored state behind
    return ok ? 1 : 0;
}

}  // extern "C"

namespace {

// Ingest-plane pane pre-reduction (windflow_tpu/ingest/coalesce.py):
// collapse one columnar chunk to per-(key, pane) sum partials over a
// dense grid, fused min/max scan + accumulate in two passes.  Values
// fold in arrival order, exactly like the engine's own pane ring.
// floor division (numpy's //): the Python fallback floors, and a
// negative timestamp must land in its containing pane, not pane 0
static inline i64 floordiv(i64 a, i64 b) {
    i64 q = a / b;
    return (a % b != 0 && ((a < 0) != (b < 0))) ? q - 1 : q;
}

template <typename V>
i64 pane_prereduce_impl(const i64* keys, const i64* tss, const V* vals,
                        i64 n, i64 pane, i64 cap, i64* out_keys,
                        i64* out_panes, double* out_sums) {
    if (n <= 0) return 0;
    i64 kmin = keys[0], kmax = keys[0], bmin = tss[0], bmax = tss[0];
    for (i64 i = 1; i < n; ++i) {
        const i64 k = keys[i], t = tss[i];
        if (k < kmin) kmin = k; else if (k > kmax) kmax = k;
        if (t < bmin) bmin = t; else if (t > bmax) bmax = t;
    }
    bmin = floordiv(bmin, pane);
    bmax = floordiv(bmax, pane);
    // range spans in UNSIGNED arithmetic: wire-fed key/ts columns can
    // legitimately span most of int64 (codec frames are unvalidated),
    // and (kmax - kmin + 1) in signed math would be UB the optimizer
    // may exploit to delete the guards below
    const uint64_t ukr = (uint64_t)kmax - (uint64_t)kmin;
    const uint64_t ubr = (uint64_t)bmax - (uint64_t)bmin;
    // sparse key/pane domain: a dense grid would be allocation-bound.
    // Comparisons are span-based (no +1, no product) so nothing wraps.
    if (ukr >= (uint64_t)(n + 1024)) return -1;
    const i64 krange = (i64)ukr + 1;
    if (ubr >= (uint64_t)((4 * n + 4096) / krange)) return -1;
    const i64 brange = (i64)ubr + 1;
    const i64 grid = krange * brange;
    std::vector<double> sums((size_t)grid, 0.0);
    std::vector<i64> counts((size_t)grid, 0);
    for (i64 i = 0; i < n; ++i) {
        const i64 idx = (floordiv(tss[i], pane) - bmin) * krange
                        + (keys[i] - kmin);
        sums[(size_t)idx] += (double)vals[i];
        counts[(size_t)idx] += 1;
    }
    i64 m = 0;
    for (i64 idx = 0; idx < grid; ++idx) {  // pane-major ascending order
        if (counts[(size_t)idx] == 0) continue;
        if (m >= cap) return -2;            // caller retries with more room
        out_keys[m] = idx % krange + kmin;
        out_panes[m] = (idx / krange + bmin) * pane;
        out_sums[m] = sums[(size_t)idx];
        ++m;
    }
    return m;
}

}  // namespace

extern "C" {

// Returns the number of partials written, -1 when the key/pane domain
// is too sparse for the dense grid (caller falls back), or -2 when
// `cap` is too small (caller retries with a larger buffer).
i64 wfn_pane_prereduce(const i64* keys, const i64* tss, const double* vals,
                       i64 n, i64 pane, i64 cap, i64* out_keys,
                       i64* out_panes, double* out_sums) {
    return pane_prereduce_impl(keys, tss, vals, n, pane, cap, out_keys,
                               out_panes, out_sums);
}

i64 wfn_pane_prereduce_f32(const i64* keys, const i64* tss,
                           const float* vals, i64 n, i64 pane, i64 cap,
                           i64* out_keys, i64* out_panes,
                           double* out_sums) {
    return pane_prereduce_impl(keys, tss, vals, n, pane, cap, out_keys,
                               out_panes, out_sums);
}

}  // extern "C"
