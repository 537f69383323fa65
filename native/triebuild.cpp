// Native trie-structure builder for the fused device commit ("turbo path").
//
// The round-1 committer spent ~9 us/node of Python on structure + RLP
// template building — the host-side wall the TPU cannot fix (round-1
// VERDICT, weak #1/#3). This C++ sweep does all per-node work at memcpy
// speed and emits flat numpy-ready arrays grouped by trie depth level:
//
//   - PACKED rows (leaves, extensions, and the rare branch with an inline
//     child): tightly concatenated RLP template bytes + row offsets +
//     digest-splice holes. No padding crosses the host->device wire; the
//     device unpacks rows by gather (reth_tpu/ops/fused_commit.py).
//   - BITMAP rows (branches whose 16 children are all hashed — the
//     overwhelming majority in a secure trie): just a 2-byte state mask +
//     child (row, nibble, src-slot) triples. The device reconstructs the
//     full branch RLP (header f9 xx xx, 33-byte refs, empty-slot 0x80,
//     empty value) from the mask alone — a ~250x H2D reduction per branch.
//
// Layout rules mirror reth_tpu/trie/node.py (yellow-paper MPT encodings)
// and the structure recursion mirrors trie/committer.py::_build; parity is
// pinned by tests/test_turbo_commit.py. Reference analogue: the alloy-trie
// HashBuilder + StateRoot walk (reference crates/trie/trie/src/trie.rs:32)
// re-designed as a host-side array producer for a device hashing plane.
//
// Secure-trie keys only: every key is exactly 32 bytes (64 nibbles), as
// produced by keccak256(address|slot) — the MerkleStage full-rebuild shape
// (reference crates/stages/stages/src/stages/merkle.rs:184).
//
// Threads: the recursion shares nothing between a branch's children but the
// append-only collectors and the slot counter, and child k's subtree takes a
// contiguous run of slots and of every level's rows. So a job of
// `threaded_job_leaves` leaves or more builds the children of its FIRST
// branch side by side, each into a Build of its own, and the same threads
// lay the pieces into the job's collectors in nibble order, slots and rows
// shifted by what the children before took: the arrays of the serial sweep,
// byte for byte, whatever the threads' timing (Build::build_children_threaded;
// tests/test_sweep_threads.py, native/triebuild_tsan.cpp).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <new>
#include <system_error>
#include <thread>
#include <type_traits>
#include <utility>
#include <vector>

namespace {

constexpr int RATE = 136;
constexpr int NIBS = 64;

struct Hole {           // digest splice target inside a packed row
    int32_t row;
    int32_t off;        // byte offset within the row's RLP
    int32_t src;        // digest-buffer slot of the child
};

struct Child {          // bitmap-branch child
    int32_t row;
    int32_t nib;
    int32_t src;
};

// A growable array of plain values on realloc(): resize() leaves new
// elements unwritten, so the threaded sweep sizes a level once and its
// threads write their own pieces (a std::vector would zero-fill: one serial
// pass over the job's output), and growing copies no byte that the
// allocator can keep in place.
template <class T>
struct Vec {
    static_assert(std::is_trivially_copyable<T>::value, "Vec holds plain values");
    T* ptr = nullptr;
    size_t len = 0, cap = 0;

    Vec() = default;
    Vec(const Vec&) = delete;
    Vec& operator=(const Vec&) = delete;
    Vec(Vec&& o) noexcept : ptr(o.ptr), len(o.len), cap(o.cap) {
        o.ptr = nullptr;
        o.len = o.cap = 0;
    }
    Vec& operator=(Vec&& o) noexcept {
        std::swap(ptr, o.ptr);
        std::swap(len, o.len);
        std::swap(cap, o.cap);
        return *this;
    }
    ~Vec() { free(ptr); }

    size_t size() const { return len; }
    bool empty() const { return len == 0; }
    T* data() { return ptr; }
    const T* data() const { return ptr; }
    T& operator[](size_t i) { return ptr[i]; }
    const T& operator[](size_t i) const { return ptr[i]; }
    const T* begin() const { return ptr; }
    const T* end() const { return ptr + len; }
    void resize(size_t n) {
        if (n > cap) {
            size_t want = std::max(n, std::max<size_t>(16, 2 * cap));
            T* grown = static_cast<T*>(realloc(ptr, want * sizeof(T)));
            if (!grown) throw std::bad_alloc();
            ptr = grown;
            cap = want;
        }
        len = n;
    }
    void push_back(const T& v) {
        resize(len + 1);
        ptr[len - 1] = v;
    }
    void append(const T* from, size_t n) {
        resize(len + n);
        memcpy(ptr + len - n, from, n * sizeof(T));
    }
};

struct Level {
    // packed group
    Vec<uint8_t> bytes;
    Vec<uint32_t> row_off;   // size rows+1
    Vec<int32_t> row_slot;
    Vec<Hole> holes;
    // bitmap group
    Vec<uint16_t> masks;
    Vec<int32_t> bmp_slot;
    Vec<Child> children;
};

struct BranchMeta {      // TrieUpdates record (reference BranchNodeCompact)
    uint32_t job;
    uint32_t rep_key;    // path = keys[rep_key][:depth]
    uint16_t depth;
    uint16_t state_mask;
    uint16_t tree_mask;
    uint16_t hash_mask;
    int32_t child_slot[16];  // slot when hashed, -1 otherwise
};

// fn(k) for every k in [0, count) on up to `threads` threads, the caller one
// of them, each taking the next k off a counter. A thread the system refuses
// is work the others take.
template <class F>
void each_on_threads(int threads, int count, F fn) {
    std::atomic<int> next{0};
    auto work = [&] {
        for (int k; (k = next.fetch_add(1, std::memory_order_relaxed)) < count;) fn(k);
    };
    std::vector<std::thread> pool;
    for (int t = 1; t < std::min(threads, count); t++) {
        try {
            pool.emplace_back(work);
        } catch (const std::system_error&) {
            break;
        }
    }
    work();
    for (auto& t : pool) t.join();
}

// A finalized child reference flowing up the recursion.
struct Ref {
    int32_t slot;              // >0 when hashed
    uint32_t inline_off;       // into scratch, when slot == 0
    uint32_t inline_len;
    bool has_branch;           // subtree contains a branch (tree_mask)
};

struct Build {
    const uint8_t* keys;
    const uint8_t* values;
    const uint64_t* val_off;
    uint32_t job;
    bool collect_meta;
    std::vector<Level> levels{NIBS + 1};
    std::vector<uint8_t> scratch;          // inline-node RLP bytes
    Vec<BranchMeta> meta;
    int32_t next_slot = 1;                 // 0 reserved dummy
    int err = 0;
    // a branch over `threaded_leaves` keys or more builds its children on
    // `threads` threads. The children's own Builds keep threads 1, so it is
    // the first branch a job's recursion meets and no other
    int threads = 1;
    uint64_t threaded_leaves = 0;

    inline uint8_t nib(uint64_t key, int k) const {
        uint8_t b = keys[key * 32 + (k >> 1)];
        return (k & 1) ? (b & 0xF) : (b >> 4);
    }

    // RLP list header for a payload of n bytes, appended to out.
    static void list_header(std::vector<uint8_t>& out, size_t n) {
        if (n <= 55) {
            out.push_back(uint8_t(0xC0 + n));
        } else if (n <= 0xFF) {
            out.push_back(0xF8);
            out.push_back(uint8_t(n));
        } else {
            out.push_back(0xF9);
            out.push_back(uint8_t(n >> 8));
            out.push_back(uint8_t(n & 0xFF));
        }
    }

    // RLP string encoding of n bytes appended to out (single byte < 0x80
    // self-encodes; the leaf value is a string item inside the node list).
    // Returns false for n > 0xFFFF: state-trie leaf values are bounded
    // (storage <= 33 B, account RLP ~110 B), so outsized values signal a
    // caller error — reported via err=4 rather than a silently wrong root.
    static bool str_item(std::vector<uint8_t>& out, const uint8_t* v, size_t n) {
        if (n == 1 && v[0] < 0x80) {
            out.push_back(v[0]);
            return true;
        }
        if (n <= 55) {
            out.push_back(uint8_t(0x80 + n));
        } else if (n <= 0xFF) {
            out.push_back(0xB8);
            out.push_back(uint8_t(n));
        } else if (n <= 0xFFFF) {
            out.push_back(0xB9);
            out.push_back(uint8_t(n >> 8));
            out.push_back(uint8_t(n & 0xFF));
        } else {
            return false;
        }
        out.insert(out.end(), v, v + n);
        return true;
    }

    // hex-prefix encoding of nibbles key[from..64) appended to out,
    // including its RLP string header. leaf => flag 0x20.
    static void path_enc(std::vector<uint8_t>& out, const Build& b, uint64_t key,
                         int from, int to, bool leaf) {
        int n = to - from;
        int enc_len = 1 + n / 2;
        uint8_t first = leaf ? 0x20 : 0x00;
        if (n & 1) first |= 0x10 | b.nib(key, from++);
        // RLP string header (enc_len 1 with byte < 0x80 self-encodes)
        if (enc_len > 1) out.push_back(uint8_t(0x80 + enc_len));
        out.push_back(first);
        for (int k = from; k < to; k += 2)
            out.push_back(uint8_t((b.nib(key, k) << 4) | b.nib(key, k + 1)));
    }

    // Finish a node whose RLP template (holes zero-filled at hole_offs) is
    // in tmp: route to the level collectors or the inline scratch.
    Ref emit(int at_depth, std::vector<uint8_t>& tmp,
             const std::vector<Hole>& node_holes, bool has_branch) {
        Ref r{};
        r.has_branch = has_branch;
        if (tmp.size() < 32) {
            r.inline_off = uint32_t(scratch.size());
            r.inline_len = uint32_t(tmp.size());
            scratch.insert(scratch.end(), tmp.begin(), tmp.end());
            return r;
        }
        Level& lv = levels[at_depth];
        if (lv.row_off.empty()) lv.row_off.push_back(0);
        int32_t row = int32_t(lv.row_off.size()) - 1;
        r.slot = next_slot++;
        lv.bytes.append(tmp.data(), tmp.size());
        lv.row_off.push_back(uint32_t(lv.bytes.size()));
        lv.row_slot.push_back(r.slot);
        for (Hole h : node_holes) {
            h.row = row;
            lv.holes.push_back(h);
        }
        return r;
    }

    // Build the subtree for keys [lo, hi) sharing the first `depth` nibbles;
    // the node sits at trie position `at_depth` nibbles deep.
    Ref build(uint64_t lo, uint64_t hi, int depth, int at_depth) {
        if (err) return Ref{};
        if (hi - lo == 1) {  // leaf
            std::vector<uint8_t> payload;
            path_enc(payload, *this, lo, depth, NIBS, true);
            if (!str_item(payload, values + val_off[lo], val_off[lo + 1] - val_off[lo])) {
                err = 4;  // oversized leaf value
                return Ref{};
            }
            std::vector<uint8_t> tmp;
            list_header(tmp, payload.size());
            tmp.insert(tmp.end(), payload.begin(), payload.end());
            std::vector<Hole> none;
            return emit(at_depth, tmp, none, false);
        }
        // common prefix of first & last key below depth (sorted => group cpl)
        int cpl = 0;
        while (depth + cpl < NIBS && nib(lo, depth + cpl) == nib(hi - 1, depth + cpl))
            cpl++;
        if (depth + cpl >= NIBS) {  // duplicate keys
            err = 2;
            return Ref{};
        }
        if (cpl > 0) {  // extension wrapping the branch below
            Ref c = build(lo, hi, depth + cpl, at_depth + cpl);
            if (err) return Ref{};
            std::vector<uint8_t> payload;
            std::vector<Hole> holes;
            path_enc(payload, *this, lo, depth, depth + cpl, false);
            if (c.slot > 0) {
                payload.push_back(0xA0);
                holes.push_back(Hole{0, 0, c.slot});  // offset fixed below
                payload.insert(payload.end(), 32, 0);
            } else {
                payload.insert(payload.end(), scratch.begin() + c.inline_off,
                               scratch.begin() + c.inline_off + c.inline_len);
            }
            std::vector<uint8_t> tmp;
            list_header(tmp, payload.size());
            // fix hole offsets: header + position within payload
            if (!holes.empty()) {
                // digest sits right after the 0xA0 marker near the end
                holes[0].off = int32_t(tmp.size() + payload.size() - 32);
            }
            tmp.insert(tmp.end(), payload.begin(), payload.end());
            return emit(at_depth, tmp, holes, c.has_branch);
        }
        // branch over the distinct nibbles at `depth`: the children's key
        // ranges, the children, then the branch's own row
        Ref kids[16];
        bool present[16] = {};
        int n_kids = 0;
        uint8_t kid_nib[16];
        uint64_t kid_lo[17];
        for (uint64_t i = lo; i < hi;) {
            uint8_t nb = nib(i, depth);
            uint64_t j = i;
            while (j < hi && nib(j, depth) == nb) j++;
            kid_nib[n_kids] = nb;
            kid_lo[n_kids++] = i;
            i = j;
        }
        kid_lo[n_kids] = hi;
        if (threads > 1 && hi - lo >= threaded_leaves) {
            build_children_threaded(n_kids, kid_nib, kid_lo, depth, at_depth, kids);
            if (err) return Ref{};
        } else {
            for (int k = 0; k < n_kids; k++) {
                kids[kid_nib[k]] = build(kid_lo[k], kid_lo[k + 1], depth + 1, at_depth + 1);
                if (err) return Ref{};
            }
        }
        uint16_t state_mask = 0;
        bool all_hashed = true;
        for (int k = 0; k < n_kids; k++) {
            uint8_t nb = kid_nib[k];
            present[nb] = true;
            state_mask |= uint16_t(1) << nb;
            if (kids[nb].slot == 0) all_hashed = false;
        }
        Ref r{};
        if (all_hashed) {
            Level& lv = levels[at_depth];
            int32_t row = int32_t(lv.masks.size());
            r.slot = next_slot++;
            lv.masks.push_back(state_mask);
            lv.bmp_slot.push_back(r.slot);
            for (int nb = 0; nb < 16; nb++)
                if (present[nb])
                    lv.children.push_back(Child{row, nb, kids[nb].slot});
        } else {
            std::vector<uint8_t> payload;
            std::vector<Hole> holes;
            for (int nb = 0; nb < 16; nb++) {
                if (!present[nb]) {
                    payload.push_back(0x80);
                    continue;
                }
                if (kids[nb].slot > 0) {
                    payload.push_back(0xA0);
                    holes.push_back(Hole{0, int32_t(payload.size()), kids[nb].slot});
                    payload.insert(payload.end(), 32, 0);
                } else {
                    payload.insert(payload.end(), scratch.begin() + kids[nb].inline_off,
                                   scratch.begin() + kids[nb].inline_off + kids[nb].inline_len);
                }
            }
            payload.push_back(0x80);  // empty branch value (secure trie)
            std::vector<uint8_t> tmp;
            list_header(tmp, payload.size());
            for (auto& h : holes) h.off += int32_t(tmp.size());
            tmp.insert(tmp.end(), payload.begin(), payload.end());
            r = emit(at_depth, tmp, holes, true);
        }
        r.has_branch = true;
        if (collect_meta) {
            BranchMeta m{};
            m.job = job;
            m.rep_key = uint32_t(lo);
            m.depth = uint16_t(at_depth);
            m.state_mask = state_mask;
            uint16_t tree = 0, hmask = 0;
            for (int nb = 0; nb < 16; nb++) {
                m.child_slot[nb] = -1;
                if (!present[nb]) continue;
                if (kids[nb].has_branch) tree |= uint16_t(1) << nb;
                if (kids[nb].slot > 0) {
                    hmask |= uint16_t(1) << nb;
                    m.child_slot[nb] = kids[nb].slot;
                }
            }
            m.tree_mask = tree;
            m.hash_mask = hmask;
            meta.push_back(m);
        }
        return r;
    }

    // The children of one branch, each built into a Build of its own (its
    // own collectors, slots from 1) and then laid into this Build's
    // collectors where the serial recursion would have put them: child k's
    // slots after those of the children before it, its rows of every level
    // after theirs. Offsets come from the children's sizes in nibble order,
    // never from which thread finished first, and the threads that built
    // the pieces copy them, so no serial pass over the output is added.
    // An error is the first failed child's in nibble order, as the serial
    // recursion stops at it.
    void build_children_threaded(int n_kids, const uint8_t* kid_nib, const uint64_t* kid_lo,
                                 int depth, int at_depth, Ref* kids) {
        std::vector<Build> parts(n_kids);
        std::vector<Ref> refs(n_kids);
        each_on_threads(threads, n_kids, [&](int k) {
            Build& c = parts[k];
            c.keys = keys;
            c.values = values;
            c.val_off = val_off;
            c.job = job;
            c.collect_meta = collect_meta;
            refs[k] = c.build(kid_lo[k], kid_lo[k + 1], depth + 1, at_depth + 1);
        });
        for (Build& c : parts)
            if (c.err) {
                err = c.err;
                return;
            }
        // what every child shifts by: slots, the records, and per level the
        // packed rows, bytes and holes and the bitmap rows and children that
        // this Build holds already plus those of the children before
        struct Base {
            size_t rows, bytes, holes, masks, children;
        };
        std::vector<int32_t> slot_base(n_kids);
        std::vector<size_t> meta_base(n_kids);
        std::vector<Base> base(size_t(n_kids) * (NIBS + 1));
        size_t n_meta = meta.size();
        for (int k = 0; k < n_kids; k++) {
            slot_base[k] = next_slot - 1;
            next_slot += parts[k].next_slot - 1;
            meta_base[k] = n_meta;
            n_meta += parts[k].meta.size();
        }
        meta.resize(n_meta);
        for (int d = at_depth + 1; d <= NIBS; d++) {
            Level& lv = levels[d];
            Base at{lv.row_slot.size(), lv.bytes.size(), lv.holes.size(), lv.masks.size(),
                    lv.children.size()};
            for (int k = 0; k < n_kids; k++) {
                const Level& c = parts[k].levels[d];
                base[size_t(k) * (NIBS + 1) + d] = at;
                at.rows += c.row_slot.size();
                at.bytes += c.bytes.size();
                at.holes += c.holes.size();
                at.masks += c.masks.size();
                at.children += c.children.size();
            }
            if (at.rows) {
                if (lv.row_off.empty()) lv.row_off.push_back(0);
                lv.row_off.resize(at.rows + 1);
            }
            lv.row_slot.resize(at.rows);
            lv.bytes.resize(at.bytes);
            lv.holes.resize(at.holes);
            lv.masks.resize(at.masks);
            lv.bmp_slot.resize(at.masks);
            lv.children.resize(at.children);
        }
        each_on_threads(threads, n_kids, [&](int k) {
            Build& c = parts[k];
            const int32_t sb = slot_base[k];
            for (int d = at_depth + 1; d <= NIBS; d++) {
                Level& to = levels[d];
                Level& from = c.levels[d];
                const Base b = base[size_t(k) * (NIBS + 1) + d];
                const size_t rows = from.row_slot.size();
                if (rows) {
                    memcpy(to.bytes.data() + b.bytes, from.bytes.data(), from.bytes.size());
                    for (size_t t = 0; t < rows; t++) {
                        to.row_off[b.rows + t + 1] = from.row_off[t + 1] + uint32_t(b.bytes);
                        to.row_slot[b.rows + t] = from.row_slot[t] + sb;
                    }
                    for (size_t t = 0; t < from.holes.size(); t++) {
                        const Hole& h = from.holes[t];
                        to.holes[b.holes + t] = Hole{h.row + int32_t(b.rows), h.off, h.src + sb};
                    }
                }
                const size_t masks = from.masks.size();
                if (masks) {
                    memcpy(to.masks.data() + b.masks, from.masks.data(), masks * 2);
                    for (size_t t = 0; t < masks; t++)
                        to.bmp_slot[b.masks + t] = from.bmp_slot[t] + sb;
                    for (size_t t = 0; t < from.children.size(); t++) {
                        const Child& ch = from.children[t];
                        to.children[b.children + t] =
                            Child{ch.row + int32_t(b.masks), ch.nib, ch.src + sb};
                    }
                }
                from = Level{};  // the piece is in place: its memory goes back now
            }
            BranchMeta* out = meta.data() + meta_base[k];
            for (const BranchMeta& m : c.meta) {
                *out = m;
                for (int nb = 0; nb < 16; nb++)
                    if (out->child_slot[nb] >= 0) out->child_slot[nb] += sb;
                out++;
            }
        });
        for (int k = 0; k < n_kids; k++) {
            Ref r = refs[k];
            if (r.slot > 0) {
                r.slot += slot_base[k];
            } else {  // an inline child: its bytes where the branch's row reads them
                const uint8_t* src = parts[k].scratch.data() + r.inline_off;
                r.inline_off = uint32_t(scratch.size());
                scratch.insert(scratch.end(), src, src + r.inline_len);
            }
            kids[kid_nib[k]] = r;
        }
    }
};

struct Handle {
    std::vector<Level> levels;     // only non-empty, deepest first
    std::vector<uint32_t> depths;
    std::vector<int32_t> root_slot;      // per job; -1 => inline/empty
    std::vector<std::vector<uint8_t>> root_inline;
    Vec<BranchMeta> meta;
    int32_t max_slot = 0;
};

}  // namespace

extern "C" {

// err: 0 ok, 1 unsorted keys, 2 duplicate keys, 3 bad input, 4 oversized
// value. start_depth: build each job's trie from nibble `start_depth` of
// its keys — the job's result is the SUBTRIE as it sits at that depth in
// the enclosing trie (leaf/ext paths are position-relative, so keys
// sharing a start_depth-nibble prefix yield exactly the embedded node).
// Chunked MerkleStage rebuilds commit per-prefix account subtries this
// way and stitch them as opaque boundaries (reth_tpu/stages/merkle.py).
// threads, threaded_job_leaves: a job of that many leaves or more builds
// its first branch's children on `threads` threads; the handle is the
// serial one byte for byte, and threads <= 1 is the serial sweep.
void* rtb_build(const uint8_t* keys, uint64_t n_keys, const uint64_t* job_off,
                uint32_t n_jobs, const uint8_t* values, const uint64_t* val_off,
                int collect_meta, int start_depth, int threads,
                uint64_t threaded_job_leaves, int* err) {
    *err = 0;
    if (!keys || !job_off || !values || !val_off || n_jobs == 0 ||
        start_depth < 0 || start_depth >= NIBS) {
        *err = 3;
        return nullptr;
    }
    Build b{};
    b.keys = keys;
    b.values = values;
    b.val_off = val_off;
    b.collect_meta = collect_meta != 0;
    b.threads = threads;
    b.threaded_leaves = threaded_job_leaves;
    auto h = new Handle();
    for (uint32_t j = 0; j < n_jobs; j++) {
        uint64_t lo = job_off[j], hi = job_off[j + 1];
        if (lo > hi || hi > n_keys) {
            *err = 3;
            delete h;
            return nullptr;
        }
        for (uint64_t i = lo + 1; i < hi; i++) {
            int c = memcmp(keys + (i - 1) * 32, keys + i * 32, 32);
            if (c >= 0) {
                *err = c == 0 ? 2 : 1;
                delete h;
                return nullptr;
            }
        }
        b.job = j;
        if (lo == hi) {
            h->root_slot.push_back(-1);
            h->root_inline.emplace_back();  // empty trie
            continue;
        }
        Ref r = b.build(lo, hi, start_depth, 0);
        if (b.err) {
            *err = b.err;
            delete h;
            return nullptr;
        }
        if (r.slot > 0) {
            h->root_slot.push_back(r.slot);
            h->root_inline.emplace_back();
        } else {
            h->root_slot.push_back(-1);
            h->root_inline.emplace_back(b.scratch.begin() + r.inline_off,
                                        b.scratch.begin() + r.inline_off + r.inline_len);
        }
    }
    for (int d = NIBS; d >= 0; d--) {
        Level& lv = b.levels[d];
        if (lv.row_slot.empty() && lv.masks.empty()) continue;
        h->levels.push_back(std::move(lv));
        h->depths.push_back(uint32_t(d));
    }
    h->meta = std::move(b.meta);
    h->max_slot = b.next_slot - 1;
    return h;
}

void rtb_free(void* hp) { delete static_cast<Handle*>(hp); }

int32_t rtb_num_levels(void* hp) {
    return int32_t(static_cast<Handle*>(hp)->levels.size());
}

int32_t rtb_max_slot(void* hp) { return static_cast<Handle*>(hp)->max_slot; }

uint32_t rtb_level_depth(void* hp, int32_t i) {
    return static_cast<Handle*>(hp)->depths[i];
}

// -- packed group -----------------------------------------------------------

uint64_t rtb_packed_bytes(void* hp, int32_t i) {
    return static_cast<Handle*>(hp)->levels[i].bytes.size();
}

uint32_t rtb_packed_rows(void* hp, int32_t i) {
    return uint32_t(static_cast<Handle*>(hp)->levels[i].row_slot.size());
}

uint32_t rtb_packed_holes(void* hp, int32_t i) {
    return uint32_t(static_cast<Handle*>(hp)->levels[i].holes.size());
}

void rtb_packed_get(void* hp, int32_t i, uint8_t* out_bytes, uint32_t* out_rowoff,
                    int32_t* out_slots) {
    Level& lv = static_cast<Handle*>(hp)->levels[i];
    memcpy(out_bytes, lv.bytes.data(), lv.bytes.size());
    memcpy(out_rowoff, lv.row_off.data(), lv.row_off.size() * 4);
    memcpy(out_slots, lv.row_slot.data(), lv.row_slot.size() * 4);
}

void rtb_packed_get_holes(void* hp, int32_t i, int32_t* row, int32_t* off,
                          int32_t* src) {
    Level& lv = static_cast<Handle*>(hp)->levels[i];
    for (size_t k = 0; k < lv.holes.size(); k++) {
        row[k] = lv.holes[k].row;
        off[k] = lv.holes[k].off;
        src[k] = lv.holes[k].src;
    }
}

// -- bitmap group -----------------------------------------------------------

uint32_t rtb_bmp_rows(void* hp, int32_t i) {
    return uint32_t(static_cast<Handle*>(hp)->levels[i].masks.size());
}

uint32_t rtb_bmp_children(void* hp, int32_t i) {
    return uint32_t(static_cast<Handle*>(hp)->levels[i].children.size());
}

void rtb_bmp_get(void* hp, int32_t i, uint16_t* masks, int32_t* slots) {
    Level& lv = static_cast<Handle*>(hp)->levels[i];
    memcpy(masks, lv.masks.data(), lv.masks.size() * 2);
    memcpy(slots, lv.bmp_slot.data(), lv.bmp_slot.size() * 4);
}

void rtb_bmp_get_children(void* hp, int32_t i, int32_t* row, int32_t* nb,
                          int32_t* src) {
    Level& lv = static_cast<Handle*>(hp)->levels[i];
    for (size_t k = 0; k < lv.children.size(); k++) {
        row[k] = lv.children[k].row;
        nb[k] = lv.children[k].nib;
        src[k] = lv.children[k].src;
    }
}

// -- roots ------------------------------------------------------------------

void rtb_roots(void* hp, int32_t* out) {
    Handle* h = static_cast<Handle*>(hp);
    memcpy(out, h->root_slot.data(), h->root_slot.size() * 4);
}

uint32_t rtb_root_inline_len(void* hp, uint32_t j) {
    return uint32_t(static_cast<Handle*>(hp)->root_inline[j].size());
}

void rtb_root_inline(void* hp, uint32_t j, uint8_t* out) {
    auto& v = static_cast<Handle*>(hp)->root_inline[j];
    memcpy(out, v.data(), v.size());
}

// -- branch meta (TrieUpdates) ---------------------------------------------

uint64_t rtb_meta_count(void* hp) {
    return static_cast<Handle*>(hp)->meta.size();
}

// packed per record: job u32, rep_key u32, depth u16, state u16, tree u16,
// hash u16, child_slot i32 x16  => 80 bytes
void rtb_meta_get(void* hp, uint8_t* out) {
    Handle* h = static_cast<Handle*>(hp);
    for (auto& m : h->meta) {
        memcpy(out, &m.job, 4); out += 4;
        memcpy(out, &m.rep_key, 4); out += 4;
        memcpy(out, &m.depth, 2); out += 2;
        memcpy(out, &m.state_mask, 2); out += 2;
        memcpy(out, &m.tree_mask, 2); out += 2;
        memcpy(out, &m.hash_mask, 2); out += 2;
        memcpy(out, m.child_slot, 64); out += 64;
    }
}

}  // extern "C"
