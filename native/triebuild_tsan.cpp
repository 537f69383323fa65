// Sanitizer stress driver for the trie-structure builder (triebuild.cpp).
//
// The rebuild pipeline (reth_tpu/trie/turbo.py RebuildPipeline) calls
// rtb_build from a THREAD POOL — concurrent sweeps over shared read-only
// key/value arrays, each producing its own handle. triebuild.cpp holds no
// global state, and this driver proves it the same way kvstore_tsan.cpp
// proves the MVCC engine: run the real access pattern under TSAN (ASan+
// UBSan fallback where gcc's libtsan breaks on the running kernel).
//
// Build + run (tests/test_turbo_pipeline.py::test_triebuild_threaded_stress):
//   g++ -std=c++17 -O1 -g -fsanitize=thread -pthread triebuild.cpp \
//       triebuild_tsan.cpp -o build/triebuild_stress && ./build/triebuild_stress
//
// Workload: N threads × R rounds. Odd threads sweep a PRIVATE key set;
// even threads all sweep the SAME shared arrays concurrently (the
// pipeline's job-list sharing). Every call asks for the THREADED sweep
// (threads 4, a threshold under the key count): threads inside threads,
// as when a pool thread meets a large job while other groups sweep. Two
// failure modes: (a) memory/race errors under the sanitizer, (b)
// nondeterminism — any round whose arrays (every level's packed bytes,
// row offsets, slots, holes, masks, children; the roots; the branch
// records) hash differently from round 0, or from the one-thread sweep of
// the same input (exit 2).

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <algorithm>
#include <thread>
#include <vector>

extern "C" {
void* rtb_build(const uint8_t* keys, uint64_t n_keys, const uint64_t* job_off,
                uint32_t n_jobs, const uint8_t* values, const uint64_t* val_off,
                int collect_meta, int start_depth, int threads,
                uint64_t threaded_job_leaves, int* err);
void rtb_free(void* h);
int32_t rtb_num_levels(void* h);
int32_t rtb_max_slot(void* h);
uint32_t rtb_level_depth(void* h, int32_t i);
uint64_t rtb_packed_bytes(void* h, int32_t i);
uint32_t rtb_packed_rows(void* h, int32_t i);
uint32_t rtb_packed_holes(void* h, int32_t i);
void rtb_packed_get(void* h, int32_t i, uint8_t* bytes, uint32_t* rowoff, int32_t* slots);
void rtb_packed_get_holes(void* h, int32_t i, int32_t* row, int32_t* off, int32_t* src);
uint32_t rtb_bmp_rows(void* h, int32_t i);
uint32_t rtb_bmp_children(void* h, int32_t i);
void rtb_bmp_get(void* h, int32_t i, uint16_t* masks, int32_t* slots);
void rtb_bmp_get_children(void* h, int32_t i, int32_t* row, int32_t* nb, int32_t* src);
void rtb_roots(void* h, int32_t* out);
uint64_t rtb_meta_count(void* h);
void rtb_meta_get(void* h, uint8_t* out);
}

constexpr int kSweepThreads = 4;
constexpr uint64_t kThreadedLeaves = 64;  // under every input's key count

static std::atomic<bool> failed{false};
static std::atomic<long> builds{0};

struct Input {
    std::vector<uint8_t> keys;     // n x 32, sorted unique
    std::vector<uint64_t> job_off; // [0, n]
    std::vector<uint8_t> values;   // 1 byte per key
    std::vector<uint64_t> val_off;
};

static Input make_input(uint64_t seed, int n) {
    // LCG-filled 32-byte keys, sorted + deduped (rtb_build requires both)
    std::vector<std::vector<uint8_t>> raw(n, std::vector<uint8_t>(32));
    uint64_t s = seed * 6364136223846793005ULL + 1442695040888963407ULL;
    for (auto& k : raw)
        for (int b = 0; b < 32; b++) {
            s = s * 6364136223846793005ULL + 1442695040888963407ULL;
            k[b] = uint8_t(s >> 33);
        }
    std::sort(raw.begin(), raw.end());
    raw.erase(std::unique(raw.begin(), raw.end()), raw.end());
    Input in;
    for (auto& k : raw) in.keys.insert(in.keys.end(), k.begin(), k.end());
    uint64_t cnt = raw.size();
    in.job_off = {0, cnt};
    in.values.resize(cnt, 0x41);  // single byte < 0x80 self-encodes
    in.val_off.resize(cnt + 1);
    for (uint64_t i = 0; i <= cnt; i++) in.val_off[i] = i;
    return in;
}

// FNV-1a over every array the getters hand out, sizes included: two handles
// with one fingerprint hold the same arrays.
struct Fingerprint {
    uint64_t h = 1469598103934665603ULL;
    void bytes(const void* p, size_t n) {
        const uint8_t* b = static_cast<const uint8_t*>(p);
        for (size_t i = 0; i < n; i++) h = (h ^ b[i]) * 1099511628211ULL;
    }
    template <class T> void vec(const std::vector<T>& v) {
        uint64_t n = v.size();
        bytes(&n, 8);
        bytes(v.data(), v.size() * sizeof(T));
    }
};

static uint64_t fingerprint(void* h) {
    Fingerprint f;
    int32_t levels = rtb_num_levels(h), slot = rtb_max_slot(h);
    f.bytes(&levels, 4);
    f.bytes(&slot, 4);
    for (int32_t i = 0; i < levels; i++) {
        uint32_t depth = rtb_level_depth(h, i);
        f.bytes(&depth, 4);
        uint32_t rows = rtb_packed_rows(h, i), holes = rtb_packed_holes(h, i);
        std::vector<uint8_t> flat(rtb_packed_bytes(h, i));
        std::vector<uint32_t> row_off(rows ? rows + 1 : 0);
        std::vector<int32_t> row_slot(rows);
        if (rows) rtb_packed_get(h, i, flat.data(), row_off.data(), row_slot.data());
        std::vector<int32_t> hr(holes), ho(holes), hs(holes);
        if (holes) rtb_packed_get_holes(h, i, hr.data(), ho.data(), hs.data());
        uint32_t bmp = rtb_bmp_rows(h, i), kids = rtb_bmp_children(h, i);
        std::vector<uint16_t> masks(bmp);
        std::vector<int32_t> bmp_slot(bmp), cr(kids), cn(kids), cs(kids);
        if (bmp) rtb_bmp_get(h, i, masks.data(), bmp_slot.data());
        if (kids) rtb_bmp_get_children(h, i, cr.data(), cn.data(), cs.data());
        f.vec(flat); f.vec(row_off); f.vec(row_slot);
        f.vec(hr); f.vec(ho); f.vec(hs);
        f.vec(masks); f.vec(bmp_slot); f.vec(cr); f.vec(cn); f.vec(cs);
    }
    int32_t root = 0;
    rtb_roots(h, &root);  // one job a build here
    f.bytes(&root, 4);
    std::vector<uint8_t> meta(rtb_meta_count(h) * 80);
    if (!meta.empty()) rtb_meta_get(h, meta.data());
    f.vec(meta);
    return f.h;
}

static bool sweep(const Input* in, int collect, int threads, uint64_t* out) {
    int err = 0;
    void* h = rtb_build(in->keys.data(), in->job_off[1], in->job_off.data(),
                        1, in->values.data(), in->val_off.data(),
                        collect, 0, threads, kThreadedLeaves, &err);
    if (!h || err) {
        std::fprintf(stderr, "build failed err=%d\n", err);
        failed.store(true);
        return false;
    }
    *out = fingerprint(h);
    rtb_free(h);
    return true;
}

static void worker(const Input* in, int rounds, int collect) {
    uint64_t serial = 0, got = 0;
    if (!sweep(in, collect, 1, &serial)) return;
    for (int r = 0; r < rounds && !failed.load(); r++) {
        if (!sweep(in, collect, kSweepThreads, &got)) return;
        // every round is held to the one-thread sweep, and so to round 0
        if (got != serial) {
            std::fprintf(stderr, "NONDETERMINISM: round %d differs from the "
                                 "one-thread sweep\n", r);
            failed.store(true);
            return;
        }
        builds.fetch_add(1, std::memory_order_relaxed);
    }
}

int main() {
    const int kThreads = 6, kRounds = 24, kKeys = 1200;
    Input shared = make_input(7, kKeys);
    std::vector<Input> privates;
    for (int t = 0; t < kThreads; t += 2)
        privates.push_back(make_input(100 + t, kKeys / 2));
    std::vector<std::thread> ts;
    size_t p = 0;
    for (int t = 0; t < kThreads; t++) {
        const Input* in = (t % 2 == 0) ? &shared : &privates[p++ % privates.size()];
        ts.emplace_back(worker, in, kRounds, t % 2);
    }
    for (auto& t : ts) t.join();
    if (failed.load()) return 2;
    std::printf("STRESS_OK builds=%ld\n", builds.load());
    return 0;
}
