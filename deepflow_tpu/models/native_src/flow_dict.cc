// Native key resolution for the dict wire (models/flow_dict.py,
// FlowDictPacker): one call per record batch maps every record's flow
// key to its dictionary index, under an exact LRU over the dictionary's
// `capacity` slots.
//
// Semantics — the pure-Python resolver (flow_dict.PyKeyTable) is the
// oracle and the tests hold the two to identical output:
//  - the batch's distinct keys resolve in ascending memcmp order of
//    their 16 key bytes (numpy's np.unique order over the V16 view);
//  - a key already present moves to the MRU end, in that order;
//  - a new key takes the lowest slot never handed out, or once every
//    slot is taken, evicts the LRU head and takes its slot;
//  - a batch whose distinct keys reach `capacity` is refused before
//    any state changes.
// Key identity is the full 16 bytes: the hash only picks the bucket.
//
// State: a linear-probing hash table (key -> slot) over >= 2x capacity
// buckets, each bucket holding (hash32 << 32 | slot) so probes compare
// hashes before touching key memory; an intrusive doubly linked LRU
// list over slots; backward-shift deletion on eviction (no tombstones).
//
// Build: utils/native_build.py (g++ -O3 -march=native -funroll-loops
//        -shared -fPIC -std=c++17), keyed by source, flags and CPU.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <new>
#include <vector>

namespace {

constexpr uint64_t kEmpty = ~0ull;
constexpr uint32_t kBuckets = 1u << 11;   // of the sort's counting pass

inline uint64_t mix64(uint64_t x) {   // splitmix64 finalizer
  x ^= x >> 30;
  x *= 0xBF58476D1CE4E5B9ull;
  x ^= x >> 27;
  x *= 0x94D049BB133111EBull;
  x ^= x >> 31;
  return x;
}

inline uint32_t hash_key(const uint32_t* k, uint64_t seed) {
  uint64_t a, b;
  std::memcpy(&a, k, 8);
  std::memcpy(&b, k + 2, 8);
  return static_cast<uint32_t>(mix64(mix64(a ^ seed) ^ b));
}

inline bool same_key(const uint32_t* a, const uint32_t* b) {
  return std::memcmp(a, b, 16) == 0;
}

// the 16 key bytes as a big-endian 128-bit number: comparing (hi, lo)
// is comparing the bytes with memcmp
struct Ordered {
  uint64_t hi, lo;
  uint32_t u;
  bool operator<(const Ordered& o) const {
    return hi != o.hi ? hi < o.hi : lo < o.lo;
  }
};

inline Ordered ordered(const uint32_t* k, uint32_t u) {
  uint64_t a, b;
  std::memcpy(&a, k, 8);
  std::memcpy(&b, k + 2, 8);
  return {__builtin_bswap64(a), __builtin_bswap64(b), u};
}

// the bucket of the sort's counting pass: the top bits of the order
inline uint32_t top_bucket(const Ordered& o) {
  return static_cast<uint32_t>(o.hi >> (64 - __builtin_ctz(kBuckets)));
}

struct Table {
  uint32_t cap;
  uint32_t used = 0;             // slots handed out (ascending)
  uint64_t seed;
  uint32_t mask;
  std::vector<uint64_t> bucket;  // hash32 << 32 | slot, or kEmpty
  std::vector<uint32_t> keys;    // 4 words per slot
  std::vector<int32_t> prev, next;
  int32_t head = -1, tail = -1;  // LRU end, MRU end
  // per-call scratch, kept to avoid reallocation
  std::vector<uint64_t> seen;    // dedupe buckets: hash32 << 32 | uid
  std::vector<uint32_t> uid;     // per record: its distinct key
  std::vector<int64_t> first;    // per distinct key: first record
  std::vector<uint32_t> uhash;
  std::vector<uint32_t> uslot;
  std::vector<Ordered> order, tmp;
  std::vector<uint32_t> count;

  Table(uint32_t capacity, uint64_t s) : cap(capacity), seed(s) {
    uint32_t nb = 16;
    while (nb < 2 * capacity) nb <<= 1;
    mask = nb - 1;
    bucket.assign(nb, kEmpty);
    keys.resize(size_t(capacity) * 4);
    prev.resize(capacity);
    next.resize(capacity);
    count.resize(kBuckets + 1);
  }

  void unlink(int32_t s) {
    if (prev[s] >= 0) next[prev[s]] = next[s]; else head = next[s];
    if (next[s] >= 0) prev[next[s]] = prev[s]; else tail = prev[s];
  }

  void push_tail(int32_t s) {
    prev[s] = tail;
    next[s] = -1;
    if (tail >= 0) next[tail] = s; else head = s;
    tail = s;
  }

  int32_t find(const uint32_t* k, uint32_t h) const {
    for (uint32_t j = h & mask;; j = (j + 1) & mask) {
      uint64_t e = bucket[j];
      if (e == kEmpty) return -1;
      uint32_t s = static_cast<uint32_t>(e);
      if (static_cast<uint32_t>(e >> 32) == h &&
          same_key(&keys[size_t(s) * 4], k))
        return static_cast<int32_t>(s);
    }
  }

  void insert(uint32_t s, const uint32_t* k, uint32_t h) {
    std::memcpy(&keys[size_t(s) * 4], k, 16);
    uint32_t j = h & mask;
    while (bucket[j] != kEmpty) j = (j + 1) & mask;
    bucket[j] = (uint64_t(h) << 32) | s;
  }

  void erase(uint32_t s) {
    uint32_t h = hash_key(&keys[size_t(s) * 4], seed);
    uint32_t i = h & mask;
    while (static_cast<uint32_t>(bucket[i]) != s ||
           static_cast<uint32_t>(bucket[i] >> 32) != h)
      i = (i + 1) & mask;
    // backward-shift deletion: pull later entries of the probe run
    // into the hole unless their home lies cyclically in (hole, j]
    for (uint32_t j = (i + 1) & mask; bucket[j] != kEmpty;
         j = (j + 1) & mask) {
      uint32_t home = static_cast<uint32_t>(bucket[j] >> 32) & mask;
      bool stays = i <= j ? (i < home && home <= j)
                          : (i < home || home <= j);
      if (!stays) {
        bucket[i] = bucket[j];
        i = j;
      }
    }
    bucket[i] = kEmpty;
  }
};

}  // namespace

extern "C" {

void* df_dict_new(uint32_t capacity, uint64_t seed) {
  if (capacity == 0 || capacity > (1u << 30)) return nullptr;
  try {
    return new Table(capacity, seed);
  } catch (const std::bad_alloc&) {
    return nullptr;
  }
}

void df_dict_free(void* t) { delete static_cast<Table*>(t); }

long df_dict_size(void* t) { return static_cast<Table*>(t)->used; }

// Resolve n records' keys ((n, 4) u32, row-major). Writes each
// record's index to rec_idx[n] and, in resolution order, the first
// record of every key new to the table to news_rows (room for n).
// Returns the number of new keys; -1 when the batch's distinct keys
// reach capacity (nothing changed), -2 when scratch memory runs out
// (nothing changed). n_uniq, size_before and evicted report the
// batch's distinct keys, the table's size before it, and how many LRU
// heads it evicted.
long df_dict_resolve(void* tp, const uint32_t* keys, long n,
                     uint32_t* rec_idx, int64_t* news_rows,
                     long* n_uniq, long* size_before, long* evicted) {
  Table& t = *static_cast<Table*>(tp);
  *size_before = t.used;
  *evicted = 0;
  *n_uniq = 0;
  if (n <= 0) return 0;
  if (n > (1L << 30)) return -2;
  try {
    uint32_t nb = 16;
    while (nb < 2 * static_cast<uint64_t>(n)) nb <<= 1;
    t.seen.assign(nb, kEmpty);
    t.uid.resize(n);
    t.first.clear();
    t.uhash.clear();
    t.first.reserve(n);          // no allocation below this point
    t.uhash.reserve(n);
    t.order.reserve(n);
    t.tmp.reserve(n);
    t.uslot.reserve(n);
  } catch (const std::bad_alloc&) {
    return -2;
  }
  const uint32_t smask = static_cast<uint32_t>(t.seen.size() - 1);
  // 1. distinct keys, each with its first record
  for (long i = 0; i < n; ++i) {
    const uint32_t* k = keys + size_t(i) * 4;
    uint32_t h = hash_key(k, t.seed);
    for (uint32_t j = h & smask;; j = (j + 1) & smask) {
      uint64_t e = t.seen[j];
      if (e == kEmpty) {
        uint32_t u = static_cast<uint32_t>(t.first.size());
        t.first.push_back(i);
        t.uhash.push_back(h);
        t.seen[j] = (uint64_t(h) << 32) | u;
        t.uid[i] = u;
        break;
      }
      uint32_t u = static_cast<uint32_t>(e);
      if (static_cast<uint32_t>(e >> 32) == h &&
          same_key(keys + size_t(t.first[u]) * 4, k)) {
        t.uid[i] = u;
        break;
      }
    }
  }
  const size_t nu = t.first.size();
  *n_uniq = static_cast<long>(nu);
  if (nu >= t.cap) return -1;
  // 2. memcmp order of the distinct keys: one counting pass on the top
  // bits, then each bucket sorted alone (~3x faster than one std::sort)
  t.order.resize(nu);
  t.uslot.resize(nu);
  t.tmp.resize(nu);
  std::fill(t.count.begin(), t.count.end(), 0);
  for (size_t u = 0; u < nu; ++u) {
    t.tmp[u] = ordered(keys + size_t(t.first[u]) * 4,
                       static_cast<uint32_t>(u));
    ++t.count[top_bucket(t.tmp[u]) + 1];
  }
  for (uint32_t b = 1; b <= kBuckets; ++b) t.count[b] += t.count[b - 1];
  for (size_t u = 0; u < nu; ++u)
    t.order[t.count[top_bucket(t.tmp[u])]++] = t.tmp[u];
  // count[b] now ends bucket b, and count[b - 1] starts it
  for (uint32_t b = 0, s = 0; b < kBuckets; s = t.count[b++])
    std::sort(t.order.begin() + s, t.order.begin() + t.count[b]);
  // 3. resolve in that order: refresh hits, assign or evict for news
  long news = 0, ev = 0;
  for (const Ordered& o : t.order) {
    const uint32_t* k = keys + size_t(t.first[o.u]) * 4;
    uint32_t h = t.uhash[o.u];
    int32_t s = t.find(k, h);
    if (s >= 0) {
      t.unlink(s);
      t.push_tail(s);
    } else {
      if (t.used < t.cap) {
        s = static_cast<int32_t>(t.used++);
      } else {
        // the head is untouched by this batch: nu < cap, and every
        // touched slot has moved to the tail
        s = t.head;
        t.unlink(s);
        t.erase(static_cast<uint32_t>(s));
        ++ev;
      }
      t.insert(static_cast<uint32_t>(s), k, h);
      t.push_tail(s);
      news_rows[news++] = t.first[o.u];
    }
    t.uslot[o.u] = static_cast<uint32_t>(s);
  }
  // 4. every record's index
  for (long i = 0; i < n; ++i) rec_idx[i] = t.uslot[t.uid[i]];
  *evicted = ev;
  return news;
}

}  // extern "C"
