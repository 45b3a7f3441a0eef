// Mergeset-style series index engine.
//
// The role of the reference's tsi mergeset index
// (engine/index/tsi/mergeset_index.go over lib/util/lifted/vm/mergeset):
// map tag postings -> series ids at high cardinality with bounded RSS.
// Design (original implementation, not a port): byte-string items kept in
//   - an in-memory sorted memtable (std::set), WAL-backed, and
//   - immutable sorted runs on disk, mmap'd, binary-searched via a
//     trailing offsets table,
// flushed and merged inline when thresholds trip. All queries are prefix
// scans; set semantics dedup across runs, so a crash between "merged run
// published" and "inputs unlinked" only costs space, never correctness.
//
// Item encodings (first byte = kind, fields length-prefixed u32le so any
// byte value — including NUL — is safe in names/values):
//   'K' <key>                -> series key item, value: sid u64le
//   'S' <sid be64>           -> reverse item, value: series key bytes
//   'I' <mst> <sid be64>     -> measurement membership posting
//   'P' <mst> <tagk> <tagv> <sid be64>  -> tag posting
//   'M' <mst>                -> measurement existence
//   'D' <sid be64>           -> tombstone (series removed)
// sid is big-endian inside sort keys so postings sort by numeric sid.
//
// C ABI (ctypes): every query fills a malloc'd buffer the caller frees
// with msi_free. Thread-safe via one mutex per index.

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <dirent.h>
#include <fcntl.h>
#include <mutex>
#include <set>
#include <string>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>
#include <unordered_set>
#include <vector>

namespace {

constexpr uint32_t RUN_MAGIC = 0x4d534931;  // "MSI1"
constexpr size_t MEMTABLE_FLUSH_ITEMS = 1 << 16;
constexpr size_t MAX_RUNS = 8;

void put_u32(std::string &s, uint32_t v) {
    char b[4];
    memcpy(b, &v, 4);
    s.append(b, 4);
}

void put_u64be(std::string &s, uint64_t v) {
    for (int i = 7; i >= 0; i--) s.push_back(char((v >> (8 * i)) & 0xff));
}

uint64_t get_u64be(const char *p) {
    uint64_t v = 0;
    for (int i = 0; i < 8; i++) v = (v << 8) | uint8_t(p[i]);
    return v;
}

void put_field(std::string &s, const char *p, size_t n) {
    put_u32(s, uint32_t(n));
    s.append(p, n);
}

// CRC32 (reflected, poly 0xEDB88320) for WAL framing.
uint32_t crc32(const uint8_t *p, size_t n) {
    static uint32_t table[256];
    static bool init = false;
    if (!init) {
        for (uint32_t i = 0; i < 256; i++) {
            uint32_t c = i;
            for (int k = 0; k < 8; k++)
                c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
            table[i] = c;
        }
        init = true;
    }
    uint32_t c = 0xffffffffu;
    for (size_t i = 0; i < n; i++) c = table[(c ^ p[i]) & 0xff] ^ (c >> 8);
    return c ^ 0xffffffffu;
}

struct Run {
    int fd = -1;
    const char *map = nullptr;
    size_t map_len = 0;
    const uint64_t *offsets = nullptr;  // item start offsets
    uint64_t count = 0;

    std::string_view item(uint64_t i) const {
        uint64_t off = offsets[i];
        uint64_t end = (i + 1 < count) ? offsets[i + 1] : offsets[count];
        return {map + off, size_t(end - off)};
    }

    void close() {
        if (map) munmap(const_cast<char *>(map), map_len);
        if (fd >= 0) ::close(fd);
        map = nullptr;
        fd = -1;
    }
};

struct Index {
    std::string dir;
    std::mutex mu;
    std::set<std::string> mem;
    std::vector<Run> runs;
    std::vector<std::string> run_paths;
    uint64_t next_sid = 1;
    uint64_t next_run = 1;
    std::unordered_set<uint64_t> tombstones;
    FILE *wal = nullptr;
    uint64_t mem_since_flush = 0;
};

// ---------------------------------------------------------------- run io

bool write_run(const std::string &path, const std::vector<std::string_view> &items,
               uint64_t max_sid) {
    std::string tmp = path + ".tmp";
    FILE *f = fopen(tmp.c_str(), "wb");
    if (!f) return false;
    uint32_t magic = RUN_MAGIC;
    fwrite(&magic, 4, 1, f);
    std::vector<uint64_t> offsets;
    offsets.reserve(items.size() + 1);
    uint64_t off = 4;
    for (auto &it : items) {
        offsets.push_back(off);
        fwrite(it.data(), 1, it.size(), f);
        off += it.size();
    }
    offsets.push_back(off);  // end sentinel
    uint64_t table_at = off;
    fwrite(offsets.data(), 8, offsets.size(), f);
    uint64_t count = items.size();
    fwrite(&count, 8, 1, f);
    fwrite(&table_at, 8, 1, f);
    fwrite(&max_sid, 8, 1, f);
    fwrite(&magic, 4, 1, f);
    if (fflush(f) != 0 || fsync(fileno(f)) != 0) {
        fclose(f);
        return false;
    }
    fclose(f);
    if (rename(tmp.c_str(), path.c_str()) != 0) return false;
    // fsync the directory: the caller truncates the WAL right after, so
    // the run's dirent must be durable first or a power loss drops both
    size_t slash = path.find_last_of('/');
    std::string dir = slash == std::string::npos ? "." : path.substr(0, slash);
    int dfd = open(dir.c_str(), O_RDONLY | O_DIRECTORY);
    if (dfd >= 0) {
        fsync(dfd);
        ::close(dfd);
    }
    return true;
}

bool open_run(const std::string &path, Run &r, uint64_t &max_sid) {
    int fd = open(path.c_str(), O_RDONLY);
    if (fd < 0) return false;
    struct stat st;
    if (fstat(fd, &st) != 0 || st.st_size < 32) {
        ::close(fd);
        return false;
    }
    size_t len = size_t(st.st_size);
    const char *m = (const char *)mmap(nullptr, len, PROT_READ, MAP_SHARED, fd, 0);
    if (m == MAP_FAILED) {
        ::close(fd);
        return false;
    }
    uint32_t magic;
    memcpy(&magic, m, 4);
    uint32_t tail_magic;
    memcpy(&tail_magic, m + len - 4, 4);
    if (magic != RUN_MAGIC || tail_magic != RUN_MAGIC) {
        munmap(const_cast<char *>(m), len);
        ::close(fd);
        return false;
    }
    uint64_t count, table_at;
    memcpy(&max_sid, m + len - 12, 8);
    memcpy(&table_at, m + len - 20, 8);
    memcpy(&count, m + len - 28, 8);
    r.fd = fd;
    r.map = m;
    r.map_len = len;
    r.count = count;
    r.offsets = (const uint64_t *)(m + table_at);
    return true;
}

// lower_bound over a run for a prefix
uint64_t run_lower_bound(const Run &r, const std::string &key) {
    uint64_t lo = 0, hi = r.count;
    while (lo < hi) {
        uint64_t mid = (lo + hi) / 2;
        if (r.item(mid) < std::string_view(key))
            lo = mid + 1;
        else
            hi = mid;
    }
    return lo;
}

bool has_prefix(std::string_view item, const std::string &prefix) {
    return item.size() >= prefix.size() &&
           memcmp(item.data(), prefix.data(), prefix.size()) == 0;
}

// ---------------------------------------------------------------- wal

void wal_append(Index *ix, const std::string &payload) {
    if (!ix->wal) return;
    uint32_t n = uint32_t(payload.size());
    uint32_t crc = crc32((const uint8_t *)payload.data(), payload.size());
    fwrite(&n, 4, 1, ix->wal);
    fwrite(&crc, 4, 1, ix->wal);
    fwrite(payload.data(), 1, payload.size(), ix->wal);
}

void wal_replay(Index *ix) {
    std::string path = ix->dir + "/wal.log";
    FILE *f = fopen(path.c_str(), "rb");
    if (!f) return;
    for (;;) {
        uint32_t n, crc;
        if (fread(&n, 4, 1, f) != 1 || fread(&crc, 4, 1, f) != 1) break;
        if (n > (1u << 24)) break;  // torn/garbage tail
        std::string payload(n, '\0');
        if (fread(&payload[0], 1, n, f) != n) break;
        if (crc32((const uint8_t *)payload.data(), n) != crc) break;
        if (payload.empty()) continue;
        ix->mem.insert(payload);
    }
    fclose(f);
}

// ---------------------------------------------------------- scan helpers

// collect all items with `prefix` across memtable + runs into out (deduped
// by std::set semantics of the caller when needed)
template <typename F>
void scan_prefix(Index *ix, const std::string &prefix, F &&emit) {
    for (auto it = ix->mem.lower_bound(prefix);
         it != ix->mem.end() && has_prefix(*it, prefix); ++it)
        emit(std::string_view(*it));
    for (auto &r : ix->runs) {
        for (uint64_t i = run_lower_bound(r, prefix);
             i < r.count && has_prefix(r.item(i), prefix); i++)
            emit(r.item(i));
    }
}

bool lookup_exact_prefix(Index *ix, const std::string &prefix,
                         std::string &item_out) {
    bool found = false;
    scan_prefix(ix, prefix, [&](std::string_view it) {
        if (!found) {
            item_out.assign(it.data(), it.size());
            found = true;
        }
    });
    return found;
}

// K items carry the sid as a trailing u64le value; after a remove +
// re-create the same key has several K items — return the live (highest
// non-tombstoned) sid, 0 if none.
uint64_t lookup_key_sid(Index *ix, const std::string &kitem) {
    uint64_t best = 0;
    scan_prefix(ix, kitem, [&](std::string_view it) {
        if (it.size() < kitem.size() + 8) return;
        uint64_t sid;
        memcpy(&sid, it.data() + it.size() - 8, 8);
        if (!ix->tombstones.count(sid) && sid > best) best = sid;
    });
    return best;
}

void rebuild_tombstones(Index *ix) {
    ix->tombstones.clear();
    std::string dpfx(1, 'D');
    scan_prefix(ix, dpfx, [&](std::string_view it) {
        if (it.size() >= 9) ix->tombstones.insert(get_u64be(it.data() + 1));
    });
}

// ------------------------------------------------------------- flush/merge

bool flush_mem(Index *ix) {
    if (ix->mem.empty()) return true;
    std::vector<std::string_view> items;
    items.reserve(ix->mem.size());
    uint64_t max_sid = ix->next_sid - 1;
    for (auto &s : ix->mem) items.emplace_back(s);
    char name[64];
    snprintf(name, sizeof name, "/run-%08llu.msi",
             (unsigned long long)ix->next_run++);
    std::string path = ix->dir + name;
    if (!write_run(path, items, max_sid)) return false;
    Run r;
    uint64_t ms;
    if (!open_run(path, r, ms)) return false;
    ix->runs.push_back(r);
    ix->run_paths.push_back(path);
    ix->mem.clear();
    // truncate the wal: its contents are now durable in the run
    if (ix->wal) fclose(ix->wal);
    std::string wal_path = ix->dir + "/wal.log";
    ix->wal = fopen(wal_path.c_str(), "wb");
    return true;
}

bool merge_runs(Index *ix) {
    // full k-way merge of every run into one (size-tiering can come
    // later; dedup + tombstone filtering happens here)
    std::vector<std::string_view> all;
    uint64_t total = 0;
    for (auto &r : ix->runs) total += r.count;
    all.reserve(total);
    for (auto &r : ix->runs)
        for (uint64_t i = 0; i < r.count; i++) all.push_back(r.item(i));
    std::sort(all.begin(), all.end());
    all.erase(std::unique(all.begin(), all.end()), all.end());
    // drop items owned by tombstoned sids (keep 'D' items themselves: a
    // sid could still appear in not-yet-merged future runs... it cannot —
    // sids are never reused — so tombstones are dropped too once applied)
    std::vector<std::string_view> kept;
    kept.reserve(all.size());
    for (auto it : all) {
        if (it.empty()) continue;
        uint64_t sid = 0;
        bool has_sid = false;
        switch (it[0]) {
            case 'K':
                if (it.size() >= 8) {
                    sid = 0;
                    memcpy(&sid, it.data() + it.size() - 8, 8);  // u64le value
                    has_sid = true;
                }
                break;
            case 'S':
                if (it.size() >= 9) {
                    sid = get_u64be(it.data() + 1);
                    has_sid = true;
                }
                break;
            case 'I':
            case 'P':
                if (it.size() >= 9) {
                    sid = get_u64be(it.data() + it.size() - 8);
                    has_sid = true;
                }
                break;
            case 'D':
                continue;  // applied below by exclusion
            default:
                break;
        }
        if (has_sid && ix->tombstones.count(sid)) continue;
        kept.push_back(it);
    }
    uint64_t max_sid = ix->next_sid - 1;
    char name[64];
    snprintf(name, sizeof name, "/run-%08llu.msi",
             (unsigned long long)ix->next_run++);
    std::string path = ix->dir + name;
    if (!write_run(path, kept, max_sid)) return false;
    Run nr;
    uint64_t ms;
    if (!open_run(path, nr, ms)) return false;
    // publish new, then retire old (crash between: duplicate data, still
    // correct under set semantics; the next merge collapses it)
    std::vector<Run> old = ix->runs;
    std::vector<std::string> old_paths = ix->run_paths;
    ix->runs = {nr};
    ix->run_paths = {path};
    for (auto &r : old) r.close();
    for (auto &p : old_paths) unlink(p.c_str());
    // the MEMTABLE may still hold items (and 'D's) for removed sids that
    // this run-merge never saw — rebuild from what remains rather than
    // clearing, or those series would resurrect
    rebuild_tombstones(ix);
    return true;
}

void maybe_compact(Index *ix) {
    if (ix->mem.size() >= MEMTABLE_FLUSH_ITEMS) flush_mem(ix);
    if (ix->runs.size() > MAX_RUNS) merge_runs(ix);
}

void insert_item(Index *ix, const std::string &item) {
    auto ins = ix->mem.insert(item);
    if (ins.second) wal_append(ix, item);
}

// --------------------------------------------------------------- C ABI

struct Buf {
    char *data;
    uint64_t len;
};

char *alloc_out(const std::string &s, uint64_t *out_len) {
    char *p = (char *)malloc(s.size() ? s.size() : 1);
    memcpy(p, s.data(), s.size());
    *out_len = s.size();
    return p;
}

}  // namespace

extern "C" {

void *msi_open(const char *dir) {
    Index *ix = new Index();
    ix->dir = dir;
    mkdir(dir, 0755);
    // discover runs
    std::vector<std::string> names;
    if (DIR *d = opendir(dir)) {
        while (dirent *e = readdir(d)) {
            std::string n = e->d_name;
            if (n.size() > 4 && n.rfind("run-", 0) == 0 &&
                n.substr(n.size() - 4) == ".msi")
                names.push_back(n);
        }
        closedir(d);
    }
    std::sort(names.begin(), names.end());
    uint64_t max_sid = 0;
    for (auto &n : names) {
        Run r;
        uint64_t ms = 0;
        std::string path = ix->dir + "/" + n;
        if (open_run(path, r, ms)) {
            ix->runs.push_back(r);
            ix->run_paths.push_back(path);
            if (ms > max_sid) max_sid = ms;
            uint64_t num = strtoull(n.c_str() + 4, nullptr, 10);
            if (num >= ix->next_run) ix->next_run = num + 1;
        }
    }
    wal_replay(ix);
    // recover next_sid + tombstones from every source
    std::string dpfx(1, 'D');
    scan_prefix(ix, dpfx, [&](std::string_view it) {
        if (it.size() >= 9) ix->tombstones.insert(get_u64be(it.data() + 1));
    });
    std::string spfx(1, 'S');
    scan_prefix(ix, spfx, [&](std::string_view it) {
        if (it.size() >= 9) {
            uint64_t sid = get_u64be(it.data() + 1);
            if (sid > max_sid) max_sid = sid;
        }
    });
    ix->next_sid = max_sid + 1;
    std::string wal_path = ix->dir + "/wal.log";
    ix->wal = fopen(wal_path.c_str(), "ab");
    return ix;
}

void msi_close(void *h) {
    Index *ix = (Index *)h;
    {
        std::lock_guard<std::mutex> g(ix->mu);
        flush_mem(ix);
        if (ix->wal) fclose(ix->wal);
        for (auto &r : ix->runs) r.close();
    }
    delete ix;
}

void msi_free(void *p) { free(p); }

// series insert: fields are length-prefixed in one blob:
//   key | mst | ntags | (tagk | tagv)*
// returns the sid (existing or new). sid_req != 0 forces the sid (replay).
static uint64_t insert_blob_locked(Index *ix, const char *blob,
                                   uint64_t blob_len, uint64_t sid_req) {
    const char *p = blob, *end = blob + blob_len;
    auto field = [&](std::string_view &out) -> bool {
        if (p + 4 > end) return false;
        uint32_t n;
        memcpy(&n, p, 4);
        p += 4;
        if (p + n > end) return false;
        out = {p, n};
        p += n;
        return true;
    };
    std::string_view key, mst;
    if (!field(key) || !field(mst)) return 0;
    uint32_t ntags = 0;
    if (p + 4 > end) return 0;
    memcpy(&ntags, p, 4);
    p += 4;

    std::string kitem(1, 'K');
    put_field(kitem, key.data(), key.size());
    uint64_t existing = lookup_key_sid(ix, kitem);
    if (existing) return existing;
    uint64_t sid = sid_req ? sid_req : ix->next_sid;
    if (sid >= ix->next_sid) ix->next_sid = sid + 1;

    std::string item = kitem;
    char sle[8];
    memcpy(sle, &sid, 8);
    item.append(sle, 8);
    insert_item(ix, item);

    // S value = the whole structured insert blob (key|mst|ntags|tags…):
    // reverse lookups parse fields instead of un-escaping key strings
    item.assign(1, 'S');
    put_u64be(item, sid);
    item.append(blob, blob_len);
    insert_item(ix, item);

    item.assign(1, 'M');
    put_field(item, mst.data(), mst.size());
    insert_item(ix, item);

    item.assign(1, 'I');
    put_field(item, mst.data(), mst.size());
    put_u64be(item, sid);
    insert_item(ix, item);

    for (uint32_t i = 0; i < ntags; i++) {
        std::string_view k, v;
        if (!field(k) || !field(v)) break;
        item.assign(1, 'P');
        put_field(item, mst.data(), mst.size());
        put_field(item, k.data(), k.size());
        put_field(item, v.data(), v.size());
        put_u64be(item, sid);
        insert_item(ix, item);
    }
    maybe_compact(ix);
    return sid;
}

uint64_t msi_insert(void *h, const char *blob, uint64_t blob_len,
                    uint64_t sid_req) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    return insert_blob_locked(ix, blob, blob_len, sid_req);
}

// Batched canonical-key ingest: keys arrive as <u32 len><bytes> entries,
// guaranteed escape-free by the caller (keys containing backslashes take
// the per-key structured path). Parsing mst,k=v,... here removes the
// per-series Python parse + pack + ctypes round-trip that dominated
// high-cardinality ingest (BASELINE.md config #5). Returns the
// number of keys processed; sids land in out_sids.
uint64_t msi_insert_keys(void *h, const char *blob, uint64_t blob_len,
                         uint64_t count, uint64_t *out_sids) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    const char *p = blob, *end = blob + blob_len;
    std::string item;
    for (uint64_t i = 0; i < count; i++) {
        if (p + 4 > end) return i;
        uint32_t klen;
        memcpy(&klen, p, 4);
        p += 4;
        if (p + klen > end) return i;
        std::string_view key(p, klen);
        p += klen;
        // build the structured blob: key | mst | ntags | (k | v)...
        size_t c = key.find(',');
        std::string_view mst =
            key.substr(0, c == std::string_view::npos ? key.size() : c);
        item.clear();
        put_field(item, key.data(), key.size());
        put_field(item, mst.data(), mst.size());
        std::string tags;
        uint32_t ntags = 0;
        size_t pos = (c == std::string_view::npos) ? key.size() : c + 1;
        while (pos < key.size()) {
            size_t nc = key.find(',', pos);
            if (nc == std::string_view::npos) nc = key.size();
            std::string_view seg = key.substr(pos, nc - pos);
            size_t eq = seg.find('=');
            if (eq != std::string_view::npos) {
                put_field(tags, seg.data(), eq);
                put_field(tags, seg.data() + eq + 1, seg.size() - eq - 1);
                ntags++;
            }
            pos = nc + 1;
        }
        char nle[4];
        memcpy(nle, &ntags, 4);
        item.append(nle, 4);
        item += tags;
        out_sids[i] = insert_blob_locked(ix, item.data(), item.size(), 0);
    }
    return count;
}

// lookup without insert; returns 0 when absent
uint64_t msi_lookup(void *h, const char *key, uint64_t key_len) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string kitem(1, 'K');
    put_field(kitem, key, key_len);
    return lookup_key_sid(ix, kitem);
}

// sid buffer queries: returns malloc'd u64le array, caller frees
static char *collect_sids(Index *ix, const std::string &prefix,
                          uint64_t *out_n) {
    std::vector<uint64_t> sids;
    scan_prefix(ix, prefix, [&](std::string_view it) {
        if (it.size() >= 8) {
            uint64_t sid = get_u64be(it.data() + it.size() - 8);
            if (!ix->tombstones.count(sid)) sids.push_back(sid);
        }
    });
    std::sort(sids.begin(), sids.end());
    sids.erase(std::unique(sids.begin(), sids.end()), sids.end());
    *out_n = sids.size();
    char *p = (char *)malloc(sids.size() * 8 + 1);
    memcpy(p, sids.data(), sids.size() * 8);
    return p;
}

// 1 when the measurement has at least one live series — early-exits the
// prefix scan, so listing measurements never decodes whole posting sets
int msi_has_live(void *h, const char *mst, uint64_t mst_len) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string prefix(1, 'I');
    put_field(prefix, mst, mst_len);
    for (auto it = ix->mem.lower_bound(prefix);
         it != ix->mem.end() && has_prefix(*it, prefix); ++it) {
        if (it->size() >= 8 &&
            !ix->tombstones.count(get_u64be(it->data() + it->size() - 8)))
            return 1;
    }
    for (auto &r : ix->runs) {
        for (uint64_t i = run_lower_bound(r, prefix);
             i < r.count && has_prefix(r.item(i), prefix); i++) {
            auto item = r.item(i);
            if (item.size() >= 8 &&
                !ix->tombstones.count(
                    get_u64be(item.data() + item.size() - 8)))
                return 1;
        }
    }
    return 0;
}

char *msi_series_ids(void *h, const char *mst, uint64_t mst_len,
                     uint64_t *out_n) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string prefix(1, 'I');
    put_field(prefix, mst, mst_len);
    return collect_sids(ix, prefix, out_n);
}

char *msi_match_eq(void *h, const char *mst, uint64_t mst_len,
                   const char *k, uint64_t k_len, const char *v,
                   uint64_t v_len, uint64_t *out_n) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string prefix(1, 'P');
    put_field(prefix, mst, mst_len);
    put_field(prefix, k, k_len);
    put_field(prefix, v, v_len);
    return collect_sids(ix, prefix, out_n);
}

// distinct length-prefixed fields at position `field_idx` under a prefix;
// used for tag_keys (idx 1 under P|mst) and tag_values (idx 2 under
// P|mst|key) and measurements (idx 0 under M). Output: concatenated
// length-prefixed distinct values in sorted-item order.
char *msi_enum_field(void *h, char kind, const char *pfx_fields,
                     uint64_t pfx_blob_len, uint32_t field_idx,
                     uint64_t *out_n, uint64_t *out_len) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string prefix(1, kind);
    prefix.append(pfx_fields, pfx_blob_len);  // already length-prefixed
    // distinct via set: the memtable and each run emit sorted slices, but
    // the concatenation is NOT globally sorted, so adjacent-dedup misses
    std::set<std::string> vals;
    scan_prefix(ix, prefix, [&](std::string_view it) {
        // walk fields to field_idx (fields start after kind byte)
        const char *p = it.data() + 1, *end = it.data() + it.size();
        std::string_view f;
        for (uint32_t i = 0; i <= field_idx; i++) {
            if (p + 4 > end) return;
            uint32_t len;
            memcpy(&len, p, 4);
            p += 4;
            if (p + len > end) return;
            f = {p, len};
            p += len;
        }
        vals.emplace(f.data(), f.size());
    });
    std::string out;
    for (auto &v : vals) put_field(out, v.data(), v.size());
    *out_n = vals.size();
    return alloc_out(out, out_len);
}

// structured series blob (key|mst|ntags|tags…) for a sid ("" when unknown)
char *msi_key_of(void *h, uint64_t sid, uint64_t *out_len) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string prefix(1, 'S');
    put_u64be(prefix, sid);
    std::string found;
    if (!lookup_exact_prefix(ix, prefix, found) ||
        ix->tombstones.count(sid)) {
        *out_len = 0;
        return (char *)malloc(1);
    }
    std::string key = found.substr(9);
    return alloc_out(key, out_len);
}

// Bulk key lookup: one call for many sids. Output buffer is a sequence
// of [u32 len][len bytes] entries aligned with the input sids; a missing
// or tombstoned sid emits len=0. Caller frees with msi_free.
char *msi_keys_of(void *h, const uint64_t *sids, uint64_t n,
                  uint64_t *out_len) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    std::string out;
    out.reserve(n * 48);
    std::string prefix;
    std::string found;
    for (uint64_t i = 0; i < n; i++) {
        prefix.assign(1, 'S');
        put_u64be(prefix, sids[i]);
        found.clear();
        uint32_t len = 0;
        std::string key;
        if (lookup_exact_prefix(ix, prefix, found) &&
            !ix->tombstones.count(sids[i])) {
            key = found.substr(9);
            len = (uint32_t)key.size();
        }
        out.append((const char *)&len, 4);
        out.append(key);
    }
    return alloc_out(out, out_len);
}

void msi_remove_sids(void *h, const uint64_t *sids, uint64_t n) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    for (uint64_t i = 0; i < n; i++) {
        ix->tombstones.insert(sids[i]);
        std::string item(1, 'D');
        put_u64be(item, sids[i]);
        insert_item(ix, item);
    }
}

void msi_flush(void *h) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    if (ix->wal) fflush(ix->wal);
    if (ix->wal) fsync(fileno(ix->wal));
}

void msi_compact(void *h) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    flush_mem(ix);
    merge_runs(ix);
}

void msi_stats(void *h, uint64_t *mem_items, uint64_t *n_runs,
               uint64_t *run_items, uint64_t *next_sid) {
    Index *ix = (Index *)h;
    std::lock_guard<std::mutex> g(ix->mu);
    *mem_items = ix->mem.size();
    *n_runs = ix->runs.size();
    uint64_t total = 0;
    for (auto &r : ix->runs) total += r.count;
    *run_items = total;
    *next_sid = ix->next_sid;
}

}  // extern "C"
