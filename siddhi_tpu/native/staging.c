/* Host-side staging kernels for the TPU streaming runtime.
 *
 * Reference role (what): the per-event hot path the JVM engine runs in
 * CORE/query/selector/GroupByKeyGenerator.java:63 (string-concat group keys),
 * CORE/util/snapshot/state/PartitionStateHolder.java:43 (keyed state maps)
 * and CORE/partition/PartitionStreamReceiver.java:100-216 (clone-per-key
 * chunk grouping).
 *
 * TPU design (how): the host must turn a raw event micro-batch into the
 * device's dense [K, E] key layout faster than the chip consumes it.  numpy
 * needed ~75ms per 524k-event batch (hash temporaries + argsort); this C
 * path is a fused single pass: FNV-style 128-bit key hashing, open-address
 * probe/insert into an INTERLEAVED cell table (h1,h2,slot in one 24-byte
 * cell, so a probe costs one cache line, not three), and counting-sort
 * grouping whose count pass is fused into the probe loop.  The column
 * gather itself happens ON DEVICE (a [K,E] gather is ~60us on TPU), so the
 * host never copies event payloads at all.
 *
 * Single-threaded by design: the driver host has one core; the win is
 * constant-factor (cache lines, fused passes), not parallelism.
 */
#include <stdint.h>
#include <string.h>
#include <stdlib.h>

#define FNV_OFF 0xCBF29CE484222325ULL
#define FNV_PRIME 0x100000001B3ULL
#define MIX 0x9E3779B97F4A7C15ULL
#define EMPTY 0ULL
#define TOMB 1ULL

/* cells: [cap2][3] u64 = {h1, h2, slot}; h1 0=empty 1=tombstone. */
#define C_H1(c, i) ((c)[(i) * 3])
#define C_H2(c, i) ((c)[(i) * 3 + 1])
#define C_SLOT(c, i) ((int32_t)(c)[(i) * 3 + 2])

/* Must match keyslots._hash_words exactly (snapshot compatibility: Python
 * rebuild/restore re-hashes with its own implementation). */
static inline uint64_t hash_words(const uint64_t *w, int64_t w8,
                                  uint64_t seed) {
    uint64_t h = FNV_OFF ^ seed;
    for (int64_t j = 0; j < w8; j++) {
        h = (h ^ w[j]) * FNV_PRIME;
        h = (h ^ (h >> 29)) * MIX;
    }
    h ^= h >> 32;
    return h;
}

/* meta: [0]=count [1]=free_top [2]=tombstones [3]=journal_len
 *       [4]=journal_overflow [5]=journal_cap
 * free_stack[free_top-1] is the next slot to pop.
 *
 * Optionally fuses the grouping count pass: when cnt/touched/group_meta are
 * non-NULL, per-slot occurrence counts accumulate during the probe loop
 * (group_meta: [0]=n_uniq out, [1]=max_count out).
 *
 * Returns number of newly inserted keys, or -1 on capacity exhaustion. */
int64_t sg_slots_for(const uint64_t *words, int64_t n, int64_t w8,
                     const uint8_t *live,
                     uint64_t *cells, int64_t cap2,
                     int64_t *cell_by_slot, uint8_t *arena,
                     int32_t *free_stack, int32_t *journal, uint8_t *used,
                     int64_t *meta, int32_t lookup_only,
                     int32_t *out_slots,
                     int32_t *cnt, int32_t *touched, int64_t *group_meta,
                     uint64_t *pcache, int64_t pc_mask) {
    const uint64_t mask = (uint64_t)(cap2 - 1);
    const int64_t wb = w8 * 8;
    int64_t inserted = 0;
    int64_t n_uniq = 0;
    int32_t maxc = 0;
    /* The cell table is far larger than L2, so nearly every probe is a
     * cache miss; hash the lookahead key and prefetch its home cell a few
     * iterations early to overlap the misses. */
    enum { LOOKAHEAD = 12 };
    for (int64_t i = 0; i < n; i++) {
        if (i + LOOKAHEAD < n && (!live || live[i + LOOKAHEAD])) {
            uint64_t ph = hash_words(words + (i + LOOKAHEAD) * w8, w8, 0);
            __builtin_prefetch(&cells[(ph & mask) * 3], 0, 1);
        }
        if (live && !live[i]) { out_slots[i] = -1; continue; }
        const uint64_t *key = words + i * w8;
        uint64_t h1 = hash_words(key, w8, 0);
        if (h1 < 2) h1 = 2;
        uint64_t h2 = hash_words(key, w8, 0xABCD);
        int32_t slot = -1;
        /* L2-resident direct-mapped cache in front of the big table:
         * events of one key cluster within a batch, so most probes hit
         * here instead of missing into the (HBM-sized) cell table.
         * Invalidated wholesale by Python on purge/rebuild/restore. */
        uint64_t pidx = (h1 & (uint64_t)pc_mask) * 3;
        if (pcache[pidx] == h1 && pcache[pidx + 1] == h2) {
            slot = (int32_t)pcache[pidx + 2];
        } else {
            /* bounded: cap2 steps visit every cell, so exceeding the bound
             * (possible when purge-churn tombstones consume the last EMPTY
             * cells) proves absence instead of spinning forever. */
            uint64_t idx = h1 & mask;
            for (int64_t probes = 0; probes < cap2; probes++) {
                uint64_t c = C_H1(cells, idx);
                if (c == h1 && C_H2(cells, idx) == h2) {
                    slot = C_SLOT(cells, idx); break;
                }
                if (c == EMPTY) break;
                idx = (idx + 1) & mask;
            }
            if (slot >= 0) {
                pcache[pidx] = h1; pcache[pidx + 1] = h2;
                pcache[pidx + 2] = (uint64_t)(uint32_t)slot;
            }
        }
        if (slot < 0 && !lookup_only) {
            if (meta[1] <= 0) return -1;          /* capacity exhausted */
            slot = free_stack[--meta[1]];
            /* insert at first EMPTY or TOMB cell */
            uint64_t j = h1 & mask;
            while (C_H1(cells, j) > TOMB) j = (j + 1) & mask;
            C_H1(cells, j) = h1; C_H2(cells, j) = h2;
            cells[j * 3 + 2] = (uint64_t)(uint32_t)slot;
            cell_by_slot[slot] = (int64_t)j;
            memcpy(arena + (int64_t)slot * wb, key, (size_t)wb);
            used[slot] = 1;
            meta[0]++;
            if (meta[3] < meta[5]) journal[meta[3]++] = slot;
            else meta[4] = 1;                     /* journal overflow */
            inserted++;
            pcache[pidx] = h1; pcache[pidx + 1] = h2;
            pcache[pidx + 2] = (uint64_t)(uint32_t)slot;
        }
        out_slots[i] = slot;
        if (cnt && slot >= 0) {                   /* fused group count */
            int32_t c2 = ++cnt[slot];
            if (c2 == 1) touched[n_uniq++] = slot;
            if (c2 > maxc) maxc = c2;
        }
    }
    if (group_meta) { group_meta[0] = n_uniq; group_meta[1] = maxc; }
    return inserted;
}

/* Rebuild the probe table from the arena (tombstone GC / restore). */
void sg_rebuild(uint64_t *cells, int64_t cap2,
                int64_t *cell_by_slot, const uint8_t *arena, int64_t w8,
                const uint8_t *used, int64_t capacity) {
    const uint64_t mask = (uint64_t)(cap2 - 1);
    memset(cells, 0, (size_t)cap2 * 24);
    for (int64_t s = 0; s < capacity; s++) {
        cell_by_slot[s] = -1;
        if (!used[s]) continue;
        const uint64_t *key = (const uint64_t *)(arena + s * w8 * 8);
        uint64_t h1 = hash_words(key, w8, 0);
        if (h1 < 2) h1 = 2;
        uint64_t h2 = hash_words(key, w8, 0xABCD);
        uint64_t j = h1 & mask;
        while (C_H1(cells, j) > TOMB) j = (j + 1) & mask;
        C_H1(cells, j) = h1; C_H2(cells, j) = h2;
        cells[j * 3 + 2] = (uint64_t)(uint32_t)s;
        cell_by_slot[s] = (int64_t)j;
    }
}

/* Standalone count pass (used when slots come from elsewhere, e.g. the
 * sharded path regrouping by local slot). */
int64_t sg_group_count(const int32_t *slots, const uint8_t *valid, int64_t n,
                       int32_t *cnt, int32_t *touched,
                       int64_t *max_count_out) {
    int64_t u = 0;
    int32_t maxc = 0;
    for (int64_t i = 0; i < n; i++) {
        int32_t s = slots[i];
        if (s < 0 || (valid && !valid[i])) continue;
        int32_t c = ++cnt[s];
        if (c == 1) touched[u++] = s;
        if (c > maxc) maxc = c;
    }
    *max_count_out = maxc;
    return u;
}

static void radix_sort_u32(uint32_t *a, int64_t n, uint32_t *tmp) {
    int64_t hist[2048], up = 1;
    while (up < n && a[up - 1] <= a[up]) up++;
    if (up >= n) return;          /* ascending as it came: a sweep's keys */
    for (int shift = 0; shift < 32; shift += 11) {
        memset(hist, 0, sizeof(hist));
        const uint32_t m = (shift + 11 >= 32) ? (0xFFFFFFFFu >> shift)
                                              : 0x7FFu;
        for (int64_t i = 0; i < n; i++)
            hist[(a[i] >> shift) & m]++;
        int64_t sum = 0;
        for (int64_t b = 0; b < 2048; b++) {
            int64_t c = hist[b]; hist[b] = sum; sum += c;
        }
        for (int64_t i = 0; i < n; i++)
            tmp[hist[(a[i] >> shift) & m]++] = a[i];
        memcpy(a, tmp, (size_t)n * 4);
    }
}

/* Fill pass: sort unique slots ascending, emit key_idx [Kb] (pad beyond
 * n_uniq), sel [Kb*E] (-1 = padding), re-zero cnt.  rank is a scratch
 * array >= capacity.  Returns 1 if slots are one contiguous ascending run
 * starting at key_idx[0] (dense fast path), else 0. */
int32_t sg_group_fill(const int32_t *slots, const uint8_t *valid, int64_t n,
                      int32_t *cnt, int32_t *rank, int32_t *touched,
                      int64_t n_uniq, int64_t Kb, int64_t E, int32_t pad,
                      int32_t *key_idx, int32_t *sel) {
    uint32_t *tmp = (uint32_t *)malloc((size_t)n_uniq * 4);
    radix_sort_u32((uint32_t *)touched, n_uniq, tmp);
    free(tmp);
    for (int64_t k = 0; k < Kb; k++)
        key_idx[k] = (k < n_uniq) ? touched[k] : pad;
    memset(sel, 0xFF, (size_t)(Kb * E) * 4);
    for (int64_t k = 0; k < n_uniq; k++) {
        rank[touched[k]] = (int32_t)k;
        cnt[touched[k]] = 0;                      /* reuse as within-counter */
    }
    for (int64_t i = 0; i < n; i++) {
        int32_t s = slots[i];
        if (s < 0 || (valid && !valid[i])) continue;
        int64_t r = rank[s];
        sel[r * E + cnt[s]++] = (int32_t)i;
    }
    for (int64_t k = 0; k < n_uniq; k++)
        cnt[touched[k]] = 0;                      /* leave cnt clean */
    return (n_uniq > 0 &&
            touched[n_uniq - 1] == touched[0] + (int32_t)(n_uniq - 1)) ? 1 : 0;
}

/* Sharded fill: the touched slots split by `slot % n_shards` into n_shards
 * rectangles [Kb, E] laid end to end, each as sg_group_fill lays the one:
 * shard d's keys are its LOCAL rows `slot / n_shards` ascending from row
 * d * Kb (the slots ascending with `slot % n_shards == d`), pads `pad`, a
 * key's events along E in batch order.  key_cnt [n_uniq] takes the events
 * of each slot, in ascending slot order (touched, sorted, is the slots);
 * shard_events [n_shards] the events routed to each shard.  Kb must hold
 * the fullest shard's keys.  Leaves cnt clean. */
void sg_group_fill_shards(const int32_t *slots, const uint8_t *valid,
                          int64_t n, int32_t *cnt, int32_t *rank,
                          int32_t *touched, int64_t n_uniq,
                          int64_t n_shards, int64_t Kb, int64_t E,
                          int32_t pad, int32_t *key_idx, int32_t *sel,
                          int32_t *key_cnt, int64_t *shard_events) {
    uint32_t *tmp = (uint32_t *)malloc((size_t)n_uniq * 4);
    radix_sort_u32((uint32_t *)touched, n_uniq, tmp);
    free(tmp);
    int64_t *fill = (int64_t *)calloc((size_t)n_shards, sizeof(int64_t));
    for (int64_t g = 0; g < n_shards * Kb; g++) key_idx[g] = pad;
    memset(sel, 0xFF, (size_t)(n_shards * Kb * E) * 4);
    for (int64_t d = 0; d < n_shards; d++) shard_events[d] = 0;
    for (int64_t k = 0; k < n_uniq; k++) {
        int32_t s = touched[k];
        int64_t d = s % n_shards;
        int64_t g = d * Kb + fill[d]++;
        key_idx[g] = (int32_t)(s / n_shards);
        rank[s] = (int32_t)g;
        key_cnt[k] = cnt[s];
        shard_events[d] += cnt[s];
        cnt[s] = 0;                               /* reuse as within-counter */
    }
    free(fill);
    for (int64_t i = 0; i < n; i++) {
        int32_t s = slots[i];
        if (s < 0 || (valid && !valid[i])) continue;
        sel[(int64_t)rank[s] * E + cnt[s]++] = (int32_t)i;
    }
    for (int64_t k = 0; k < n_uniq; k++)
        cnt[touched[k]] = 0;                      /* leave cnt clean */
}

/* Tiered fill: the touched keys split by their count into n_tiers classes
 * — class t holds the keys with count <= hi[t] that no earlier class took
 * (hi ascending; the last class takes the rest) — each class its own
 * [Kb[t], E[t]] rectangle laid as sg_group_fill lays the one: slots
 * ascending, a key's events along E in batch order.  key_idx and sel are
 * the classes' buffers end to end, class t's rows from key_off[t] and
 * its cells from sel_off[t].  A send whose hottest key has a thousand
 * events and whose other keys have one is then laid out in cells of the
 * order of its events, not keys x hottest count.  Leaves cnt clean. */
void sg_group_fill_tiers(const int32_t *slots, const uint8_t *valid,
                         int64_t n, int32_t *cnt, int32_t *rank,
                         int32_t *touched, int64_t n_uniq,
                         const int32_t *hi, int64_t n_tiers,
                         const int64_t *Kb, const int64_t *E,
                         const int64_t *key_off, const int64_t *sel_off,
                         int32_t pad, int32_t *key_idx, int32_t *sel) {
    uint32_t *tmp = (uint32_t *)malloc((size_t)n_uniq * 4);
    radix_sort_u32((uint32_t *)touched, n_uniq, tmp);
    free(tmp);
    const int64_t last = n_tiers - 1;
    int64_t rows = key_off[last] + Kb[last];
    int64_t cells = sel_off[last] + Kb[last] * E[last];
    for (int64_t g = 0; g < rows; g++) key_idx[g] = pad;
    memset(sel, 0xFF, (size_t)cells * 4);
    int64_t fill[16] = {0};
    for (int64_t k = 0; k < n_uniq; k++) {
        int32_t s = touched[k];
        int64_t t = 0;
        while (t < last && cnt[s] > hi[t]) t++;
        int64_t g = key_off[t] + fill[t]++;
        key_idx[g] = s;
        rank[s] = (int32_t)g;
        cnt[s] = 0;                               /* reuse as within-counter */
    }
    for (int64_t i = 0; i < n; i++) {
        int32_t s = slots[i];
        if (s < 0 || (valid && !valid[i])) continue;
        int64_t g = rank[s], t = 0;
        while (t < last && g >= key_off[t + 1]) t++;
        sel[sel_off[t] + (g - key_off[t]) * E[t] + cnt[s]++] = (int32_t)i;
    }
    for (int64_t k = 0; k < n_uniq; k++)
        cnt[touched[k]] = 0;                      /* leave cnt clean */
}
