/* One-pass native tokenizer for the NDJSON trace scanner.
 *
 * Parses the strict TRACE_SCHEMA v0 subset that repro/trace/scan.py
 * accepts -- one compact JSON object per line (each line newline-ended
 * but the last, which may end the input), no
 * escapes, no whitespace outside strings, the keys fn/bb/pp/op/def/uses
 * each exactly once, def_ty/use_tys at most once, string values (def may
 * be null), uses/use_tys arrays of strings, tokens within SYM_W bytes and
 * pp within PP_W -- and checks each record as the sequential interpreter
 * would: use_tys parallel to uses, pp == "<fn>:<bb>:i<digits>", and the
 * program-point order within a (fn, bb) run, a rewind to the run's first
 * index being block re-entry.  Any byte outside the subset stops the pass
 * with a nonzero status; the caller then re-parses the whole file with
 * the sequential interpreter, which owns every diagnostic.
 *
 * Tokens are interned per class by open-addressing hash tables: functions,
 * blocks (scoped by function), ops, types and symbols (SSA ids, scoped by
 * function).  Ids are dense in order of first appearance; the def-table
 * replay reads only their equality, never their order.  The outputs are
 * int32 columns per record (op, def symbol, def_ty type) and per use
 * (record, symbol, type), and each table's first occurrence as an offset
 * and length into the input, so the caller decodes each unique op, type
 * and live-in name once.
 */
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#define SYM_W 24                /* max bytes for ids/ops/types */
#define PP_W 48                 /* max bytes for pp tokens */
#define MAX_PP_DIGITS 18        /* the pp index fits an int64 */

enum { K_FN, K_BB, K_PP, K_OP, K_DEF, K_USES, K_DEFTY, K_USETYS };
#define REQUIRED_KEYS 0x3Fu     /* fn, bb, pp, op, def, uses */

/* output columns, in the order trace_scan_take numbers them */
enum { V_REC_OP, V_REC_DEF, V_REC_DEFTY, V_USE_REC, V_USE_SYM, V_USE_TY,
       N_VEC };
enum { T_FN, T_BB, T_OP, T_TY, T_SYM, N_TAB };

/* info[] layout filled by trace_scan */
enum { I_STATUS, I_LINES, I_VOID, I_RECORDS, I_USES, I_TAB0 };

enum { OK = 0, OUTSIDE_SUBSET = 1, NO_MEMORY = 2 };

typedef struct { int32_t *a; int64_t n, cap; } Vec;

typedef struct { uint32_t hash; int32_t id; } Slot;  /* id -1: empty */
typedef struct { uint64_t head; int32_t off, len, aux; } Entry;

typedef struct {
    Entry *e;                   /* id -> first occurrence and scope */
    int64_t n, ecap;
    Slot *slot;                 /* open addressing, load under 1/2 */
    int64_t cap;                /* slots, a power of two */
} Table;

typedef struct {
    Vec v[N_VEC];
    Table t[N_TAB];
    Vec use_off, use_len, uty_off, uty_len;     /* one record's arrays */
} Ctx;

static int push(Vec *v, int32_t x) {
    if (v->n == v->cap) {
        int64_t cap = v->cap ? 2 * v->cap : 1024;
        int32_t *a = realloc(v->a, (size_t)cap * sizeof *a);
        if (!a)
            return 0;
        v->a = a;
        v->cap = cap;
    }
    v->a[v->n++] = x;
    return 1;
}

/* the token's first eight bytes, zero past its end */
static inline uint64_t head8(const uint8_t *b, int64_t n, int32_t off,
                             int32_t len) {
    uint64_t w = 0;
    if (off + 8 <= n) {
        memcpy(&w, b + off, 8);
        if (len < 8)
            w &= len ? ~0ull >> (64 - 8 * len) : 0;
    } else
        memcpy(&w, b + off, (size_t)(len < 8 ? len : 8));
    return w;
}

static inline uint32_t hash_token(const uint8_t *p, uint64_t head,
                                  int32_t len, int32_t aux) {
    uint64_t h = (head ^ ((uint64_t)(uint32_t)aux << 32 | (uint32_t)len))
                 * 0xFF51AFD7ED558CCDull;
    for (int32_t j = 8; j < len; j += 8) {     /* tokens over 8 bytes */
        uint64_t w = 0;
        memcpy(&w, p + j, (size_t)(len - j < 8 ? len - j : 8));
        h = ((h ^ (h >> 32)) ^ w) * 0xC4CEB9FE1A85EC53ull;
    }
    h ^= h >> 29;
    h *= 0x9E3779B97F4A7C15ull;
    return (uint32_t)(h >> 32);
}

static Slot *empty_slots(int64_t cap) {
    Slot *slot = malloc((size_t)cap * sizeof *slot);
    if (slot)
        for (int64_t i = 0; i < cap; i++)
            slot[i].id = -1;
    return slot;
}

static int table_init(Table *t, int64_t expect) {
    t->cap = 1024;
    while (t->cap < 2 * expect)
        t->cap *= 2;
    t->slot = empty_slots(t->cap);
    return t->slot != NULL;
}

static int table_grow(Table *t) {
    int64_t cap = 2 * t->cap;
    uint64_t mask = (uint64_t)(cap - 1);
    Slot *slot = empty_slots(cap);
    if (!slot)
        return 0;
    for (int64_t i = 0; i < t->cap; i++) {
        if (t->slot[i].id < 0)
            continue;
        uint64_t j = t->slot[i].hash & mask;
        while (slot[j].id >= 0)
            j = (j + 1) & mask;
        slot[j] = t->slot[i];
    }
    free(t->slot);
    t->slot = slot;
    t->cap = cap;
    return 1;
}

static inline int same_entry(const Entry *e, const uint8_t *b, int32_t off,
                             uint64_t head, int32_t len, int32_t aux) {
    return e->head == head && e->len == len && e->aux == aux
           && (len <= 8 || !memcmp(b + e->off + 8, b + off + 8,
                                   (size_t)(len - 8)));
}

/* id of the token b[off, off+len) scoped by aux; -1 when out of memory */
static int32_t intern(Table *t, const uint8_t *b, int64_t n, int32_t off,
                      int32_t len, int32_t aux) {
    uint64_t head = head8(b, n, off, len);
    uint32_t h = hash_token(b + off, head, len, aux);
    uint64_t mask = (uint64_t)(t->cap - 1);
    uint64_t i = h & mask;
    for (; t->slot[i].id >= 0; i = (i + 1) & mask)
        if (t->slot[i].hash == h
                && same_entry(&t->e[t->slot[i].id], b, off, head, len, aux))
            return t->slot[i].id;
    if (t->n == t->ecap) {
        int64_t ecap = t->ecap ? 2 * t->ecap : 1024;
        Entry *e = realloc(t->e, (size_t)ecap * sizeof *e);
        if (!e)
            return -1;
        t->e = e;
        t->ecap = ecap;
    }
    int32_t id = (int32_t)t->n++;
    t->e[id] = (Entry){head, off, len, aux};
    t->slot[i] = (Slot){h, id};
    if (2 * t->n > t->cap && !table_grow(t))
        return -1;
    return id;
}

/* length of the valid UTF-8 sequence at p (as Python's strict decoder
 * reads it: no overlongs, no surrogates, nothing past U+10FFFF), or 0 */
static int utf8_seq(const uint8_t *p, int64_t avail) {
    uint8_t c = p[0];
    int k;
    uint8_t lo = 0x80, hi = 0xBF;
    if (c >= 0xC2 && c <= 0xDF)
        k = 2;
    else if (c >= 0xE0 && c <= 0xEF) {
        k = 3;
        if (c == 0xE0)
            lo = 0xA0;
        else if (c == 0xED)
            hi = 0x9F;
    } else if (c >= 0xF0 && c <= 0xF4) {
        k = 4;
        if (c == 0xF0)
            lo = 0x90;
        else if (c == 0xF4)
            hi = 0x8F;
    } else
        return 0;
    if (avail < k || p[1] < lo || p[1] > hi)
        return 0;
    for (int j = 2; j < k; j++)
        if ((p[j] & 0xC0) != 0x80)
            return 0;
    return k;
}

/* index of the quote closing the string whose first byte is b[i], or -1
 * for an escape, a control byte, invalid UTF-8 or the end of input */
static int64_t string_end(const uint8_t *b, int64_t i, int64_t n) {
    for (; i < n; i++) {
        uint8_t c = b[i];
        if (c == '"')
            return i;
        if (c == '\\' || c < 0x20)
            return -1;
        if (c >= 0x80) {
            int k = utf8_seq(b + i, n - i);
            if (!k)
                return -1;
            i += k - 1;
        }
    }
    return -1;
}

static int key_of(const uint8_t *s, int64_t len) {
    switch (len) {
    case 2:
        if (!memcmp(s, "fn", 2)) return K_FN;
        if (!memcmp(s, "bb", 2)) return K_BB;
        if (!memcmp(s, "pp", 2)) return K_PP;
        if (!memcmp(s, "op", 2)) return K_OP;
        return -1;
    case 3:
        return memcmp(s, "def", 3) ? -1 : K_DEF;
    case 4:
        return memcmp(s, "uses", 4) ? -1 : K_USES;
    case 6:
        return memcmp(s, "def_ty", 6) ? -1 : K_DEFTY;
    case 7:
        return memcmp(s, "use_tys", 7) ? -1 : K_USETYS;
    }
    return -1;
}

typedef struct {
    int32_t run_fb;             /* (fn, bb) block id of the current run */
    int64_t run_idx, run_first; /* last and first pp index of the run */
    int64_t lines, void_defs;
} State;

/* `["s", ...]` at b[i]: pushes each string's offset and length; returns
 * the index after ']' or -1 */
static int64_t string_array(const uint8_t *b, int64_t i, int64_t n,
                            Vec *off, Vec *len, int *oom) {
    if (i >= n || b[i] != '[')
        return -1;
    if (++i < n && b[i] == ']')
        return i + 1;
    for (;;) {
        if (i >= n || b[i] != '"')
            return -1;
        int64_t e = string_end(b, i + 1, n);
        if (e < 0 || e - i - 1 > SYM_W)
            return -1;
        if (!push(off, (int32_t)(i + 1)) || !push(len, (int32_t)(e - i - 1))) {
            *oom = 1;
            return -1;
        }
        i = e + 1;
        if (i < n && b[i] == ',') {
            i++;
            continue;
        }
        if (i < n && b[i] == ']')
            return i + 1;
        return -1;
    }
}

/* the record whose '{' is b[i]: validated, interned and appended; returns
 * the index after its newline (or n), or -1 with *status set */
static int64_t record(Ctx *c, State *s, const uint8_t *b, int64_t i,
                      int64_t n, int *status) {
    int32_t off[8] = {0}, len[8] = {0};
    unsigned seen = 0;
    int def_null = 0, oom = 0;
    c->use_off.n = c->use_len.n = c->uty_off.n = c->uty_len.n = 0;
    *status = OUTSIDE_SUBSET;
    i++;
    for (;;) {
        if (i >= n || b[i] != '"')
            return -1;
        int64_t e = string_end(b, i + 1, n);
        if (e < 0)
            return -1;
        int k = key_of(b + i + 1, e - i - 1);
        if (k < 0 || (seen >> k) & 1u)
            return -1;
        seen |= 1u << k;
        i = e + 1;
        if (i >= n || b[i] != ':')
            return -1;
        i++;
        if (k == K_USES)
            i = string_array(b, i, n, &c->use_off, &c->use_len, &oom);
        else if (k == K_USETYS)
            i = string_array(b, i, n, &c->uty_off, &c->uty_len, &oom);
        else if (k == K_DEF && n - i >= 4 && !memcmp(b + i, "null", 4)) {
            def_null = 1;
            i += 4;
        } else {
            if (i >= n || b[i] != '"')
                return -1;
            e = string_end(b, i + 1, n);
            if (e < 0 || e - i - 1 > (k == K_PP ? PP_W : SYM_W))
                return -1;
            off[k] = (int32_t)(i + 1);
            len[k] = (int32_t)(e - i - 1);
            i = e + 1;
        }
        if (i < 0) {
            if (oom)
                *status = NO_MEMORY;
            return -1;
        }
        if (i < n && b[i] == ',') {
            i++;
            continue;
        }
        if (i < n && b[i] == '}')
            break;
        return -1;
    }
    if (++i < n && b[i] != '\n')
        return -1;          /* one record a line; the last may end the input */
    if ((seen & REQUIRED_KEYS) != REQUIRED_KEYS)
        return -1;
    int64_t n_uses = c->use_off.n;
    if ((seen >> K_USETYS) & 1u && c->uty_off.n != n_uses)
        return -1;

    /* pp == "<fn>:<bb>:i<digits>" */
    const uint8_t *pp = b + off[K_PP];
    int64_t head = (int64_t)len[K_FN] + len[K_BB] + 3;
    int64_t ndig = len[K_PP] - head;
    if (ndig < 1 || ndig > MAX_PP_DIGITS
            || memcmp(pp, b + off[K_FN], (size_t)len[K_FN])
            || pp[len[K_FN]] != ':'
            || memcmp(pp + len[K_FN] + 1, b + off[K_BB], (size_t)len[K_BB])
            || pp[head - 2] != ':' || pp[head - 1] != 'i')
        return -1;
    int64_t idx = 0;
    for (int64_t j = head; j < len[K_PP]; j++) {
        if (pp[j] < '0' || pp[j] > '9')
            return -1;
        idx = idx * 10 + (pp[j] - '0');
    }

    *status = NO_MEMORY;
    int32_t fn = intern(&c->t[T_FN], b, n, off[K_FN], len[K_FN], -1);
    int32_t fb = fn < 0 ? -1 : intern(&c->t[T_BB], b, n, off[K_BB], len[K_BB], fn);
    if (fb < 0)
        return -1;

    /* program-point order inside one contiguous (fn, bb) run */
    int same_run = fb == s->run_fb;
    if (same_run && idx <= s->run_idx) {
        if (idx > s->run_first) {
            *status = OUTSIDE_SUBSET;
            return -1;      /* out of order: the interpreter reports it */
        }
        same_run = 0;       /* block re-entry */
    }
    if (!same_run)
        s->run_first = idx;
    s->run_fb = fb;
    s->run_idx = idx;

    int32_t rec = (int32_t)c->v[V_REC_OP].n;
    int32_t op = intern(&c->t[T_OP], b, n, off[K_OP], len[K_OP], 0);
    int32_t def = -1, defty = 0;
    if (def_null)
        s->void_defs++;
    else
        def = intern(&c->t[T_SYM], b, n, off[K_DEF], len[K_DEF], fn);
    if ((seen >> K_DEFTY) & 1u)
        defty = intern(&c->t[T_TY], b, n, off[K_DEFTY], len[K_DEFTY], 0) + 1;
    if (op < 0 || (!def_null && def < 0) || defty < 0
            || !push(&c->v[V_REC_OP], op) || !push(&c->v[V_REC_DEF], def)
            || !push(&c->v[V_REC_DEFTY], defty))
        return -1;
    for (int64_t u = 0; u < n_uses; u++) {
        int32_t sym = intern(&c->t[T_SYM], b, n, c->use_off.a[u],
                             c->use_len.a[u], fn);
        int32_t ty = 0;
        if ((seen >> K_USETYS) & 1u)
            ty = intern(&c->t[T_TY], b, n, c->uty_off.a[u], c->uty_len.a[u], 0)
                 + 1;
        if (sym < 0 || ty < 0 || !push(&c->v[V_USE_REC], rec)
                || !push(&c->v[V_USE_SYM], sym) || !push(&c->v[V_USE_TY], ty))
            return -1;
    }
    *status = OK;
    return i < n ? i + 1 : n;
}

static void vec_free(Vec *v) {
    free(v->a);
}

void trace_scan_free(void *ctx) {
    Ctx *c = ctx;
    if (!c)
        return;
    for (int k = 0; k < N_VEC; k++)
        vec_free(&c->v[k]);
    for (int k = 0; k < N_TAB; k++) {
        free(c->t[k].e);
        free(c->t[k].slot);
    }
    vec_free(&c->use_off);
    vec_free(&c->use_len);
    vec_free(&c->uty_off);
    vec_free(&c->uty_len);
    free(c);
}

/* Tokenize buf[0, n).  Fills info (I_STATUS, I_LINES, I_VOID, I_RECORDS,
 * I_USES, then each table's size) and returns the context that
 * trace_scan_take reads, or NULL when out of memory.  A nonzero status
 * means the input is outside the subset (or memory ran out). */
void *trace_scan(const uint8_t *b, int64_t n, int64_t *info) {
    Ctx *c = calloc(1, sizeof *c);
    if (!c)
        return NULL;
    memset(info, 0, (size_t)(I_TAB0 + N_TAB) * sizeof *info);
    for (int k = 0; k < N_TAB; k++)      /* symbols: about one a record */
        if (!table_init(&c->t[k], k == T_SYM ? n / 128 : 0)) {
            trace_scan_free(c);
            return NULL;
        }
    State s = {-1, -1, -1, 0, 0};
    int status = OK;
    if (n > INT32_MAX)
        status = OUTSIDE_SUBSET;
    for (int64_t i = 0; status == OK && i < n;) {
        if (b[i] == '\n') {         /* blank line */
            s.lines++;
            i++;
        } else if (b[i] != '{')
            status = OUTSIDE_SUBSET;
        else if ((i = record(c, &s, b, i, n, &status)) >= 0)
            s.lines++;
    }
    if (status == OK && c->v[V_REC_OP].n == 0)
        status = OUTSIDE_SUBSET;    /* no record: the interpreter decides */
    info[I_STATUS] = status;
    info[I_LINES] = s.lines;
    info[I_VOID] = s.void_defs;
    info[I_RECORDS] = c->v[V_REC_OP].n;
    info[I_USES] = c->v[V_USE_REC].n;
    for (int k = 0; k < N_TAB; k++)
        info[I_TAB0 + k] = c->t[k].n;
    return c;
}

/* Copy column `which` into dst: 0..N_VEC-1 the record and use columns,
 * then for each table in order its offsets and its lengths. */
void trace_scan_take(void *ctx, int32_t which, int32_t *dst) {
    Ctx *c = ctx;
    if (which < N_VEC) {
        if (c->v[which].n)
            memcpy(dst, c->v[which].a, (size_t)c->v[which].n * sizeof *dst);
        return;
    }
    const Table *t = &c->t[(which - N_VEC) / 2];
    int lengths = (which - N_VEC) & 1;
    for (int64_t i = 0; i < t->n; i++)
        dst[i] = lengths ? t->e[i].len : t->e[i].off;
}
