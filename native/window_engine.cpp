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

#include <algorithm>
#include <chrono>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <limits>
#include <map>
#include <new>
#include <numeric>
#include <vector>

namespace {

using i64 = long long;

constexpr double INF = std::numeric_limits<double>::infinity();

inline i64 now_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
        std::chrono::steady_clock::now().time_since_epoch()).count();
}

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
};

struct Desc {
    i64 key, lwid;
    int32_t slot;
};

enum class Kind : int { SUM = 0, COUNT = 1, MAX = 2, MIN = 3, MEAN = 4 };

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
                          || k == Kind::MIN)),
          kind(k), pane(std::gcd(w, s)) {
        ppw = win / pane;
        pshift = (pane & (pane - 1)) == 0 ? __builtin_ctzll(pane) : -1;
        neutral = kind == Kind::MAX ? -INF : kind == Kind::MIN ? INF : 0.0;
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
    void evict(int32_t slot) {
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
    inline i64 ring_in() const { return is_tb ? RING_IN : 0; }

    // the lanes of a block of `cap` panes, one behind the other
    static inline Ring lanes_of(char* block, i64 cap) {
        double* acc = reinterpret_cast<double*>(block);
        i64* cnt = reinterpret_cast<i64*>(acc + cap);
        return Ring{acc, cnt, cnt + cap, cnt + 2 * cap};
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
    }

    // The ring into a block of `cap` panes (its `room` stored panes
    // with it): out of the key state, or out of a block it outgrew.
    // The slot's spare block is taken where it is large enough.
    void spill(KeyState& st, i64 cap) {
        const Ring old = ring(st);
        const i64 lanes = is_tb ? 2 : 4;
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
        fold_key(d, st, pt, hi_rel);
        if (look) d_single[d] = 2;
    }

    // The key's partial of this call into its pane with one combine,
    // where that gives the bits the one-by-one fold would: every tuple
    // of the key accepted and in the one pane `hi_rel`, on a lane whose
    // pane state is a count and an order-free value.  COUNT adds whole
    // numbers (exact in a double), MAX / MIN keep the first of equal
    // extremes as the one-by-one fold does.  Else the key is marked and
    // its tuples take the second walk.
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
        double& acc = r.acc[hi_rel];
        switch (kind) {
            case Kind::COUNT: acc += (double)pt.count; break;
            case Kind::MAX: if (pt.ext > acc) acc = pt.ext; break;
            default: if (pt.ext < acc) acc = pt.ext; break;  // MIN
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
    template <bool IDS, int EXT, bool SEL, bool AHEAD, typename TV>
    void gather(const i64* bkeys, const i64* ids, const TV* vals, i64 n,
                Sel s) {
        const bool ck = SEL && !(s.through & SEL_KEYS);   // compact columns
        const bool ci = SEL && !(s.through & SEL_IDS);
        const bool cv = SEL && !(s.through & SEL_VALS);
        auto walk = [&](i64 from, i64 to) {
            for (i64 j = from; j < to; ++j) {
                const i64 r = SEL ? s.rows[j] : j;
                const int32_t d = dense_of(bkeys[ck ? j : r]);
                slot_of[j] = d;
                Part& pt = parts[d];
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
        if (runs_ahead())
            ingest_walks<SEL, true>(bkeys, ids, tss, vals, n, s);
        else
            ingest_walks<SEL, false>(bkeys, ids, tss, vals, n, s);
    }

    template <bool SEL, bool AHEAD, typename TV>
    void ingest_walks(const i64* bkeys, const i64* ids, const i64* tss,
                      const TV* vals, i64 n, Sel s) {
        call_begins();
        ++call_id;
        parts.clear();
        d_slot.clear();
        d_state.clear();
        opened_now.clear();
        if ((i64)slot_of.size() < n) slot_of.resize(n);
        if (renumber)
            gather<false, 0, SEL, AHEAD>(bkeys, ids, vals, n, s);
        else if (by_key_lane && kind == Kind::MAX)
            gather<true, 1, SEL, AHEAD>(bkeys, ids, vals, n, s);
        else if (by_key_lane && kind == Kind::MIN)
            gather<true, 2, SEL, AHEAD>(bkeys, ids, vals, n, s);
        else
            gather<true, 0, SEL, AHEAD>(bkeys, ids, vals, n, s);
        if (stream_rule) {
            if (SEL && (s.through & SEL_IDS))
                note_late<true>(ids, s.rows, n);
            else
                note_late<false>(ids, nullptr, n);
        }
        piece_ends(tuple_walk_ns);
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
                prepare(d);
            }
        } else {
            // `open`: the keys this call created, timed apart (their
            // anchor and their ring); the others after them
            const i64 t0 = now_ns();
            for (int32_t d : opened_now) prepare(d);
            open_ns += now_ns() - t0;
            std::size_t o = 0;  // opened_now is ascending
            for (std::size_t d = 0; d < nd; ++d) {
                ahead_of(d);
                if (o < opened_now.size() && (std::size_t)opened_now[o] == d)
                    ++o;
                else
                    prepare(d);
            }
        }
        probes_ahead = (i64)(nd - opened_now.size()) * AHEAD_BLOCK > n;
        // `open` inside the visit by key: the keys the call created and
        // the anchors prepare() moved back; inside the second walk: the
        // anchors fold_singly() moved back
        piece_ends(key_walk_ns);
        if (n_single) fold_singly<SEL>(ids, tss, vals, n, s);
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
    template <bool SEL, typename TV>
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
    inline void retire(KeyState& st, int32_t slot) {
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
    }

    // Stage up to max_windows ready windows as pane partials.
    // Returns the number staged.  A partial take of a long `ready`
    // list costs the windows it takes.
    i64 flush(i64 max_windows) {
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
            if (is_tb) retire(st, slot);
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
        if (!is_tb)  // the CB lane read the rings' stamps just above
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
            std::vector<i64> cnt, lid, lts;
            if (!get(p, end, st.next_fire) || !get(p, end, st.anchor)
                || !get(p, end, st.opened_max) || !get(p, end, st.max_id)
                || !get(p, end, st.pane_base) || !get(p, end, st.arrivals)
                || !get(p, end, st.staged_upto)
                || !get_vec(p, end, acc) || !get_vec(p, end, cnt)
                || !get_vec(p, end, lid) || !get_vec(p, end, lts))
                return false;
            if (cnt.size() != acc.size() || lid.size() != lts.size()
                || acc.size() > (std::size_t)INT32_MAX)
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
    // and `open_ns` / `trigger_ns` as they stood then
    struct CallClock {
        i64 began, mark, open_began, open_mark, trigger_began;
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
        const CallClock& c = call_clock;
        ingest_ns += (now_ns() - c.began) - (open_ns - c.open_began)
            - (trigger_ns - c.trigger_began);
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
// what disorder it met, into out[24].  The first nineteen: nanoseconds
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
// windows flush() staged.  Behind those nineteen, into out[19..24): what
// a batch call took less its `open` and `trigger` (ingest), of that the
// walks by the tuple and the walks by the key, what flush() took less
// its `evict` (stage), nanoseconds on the same clock, and the ring
// elements retire() moved down (Engine, beside open_ns).
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
    out[19] = e.ingest_ns;
    out[20] = e.tuple_walk_ns;
    out[21] = e.key_walk_ns;
    out[22] = e.stage_ns;
    out[23] = e.panes_shifted;
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
