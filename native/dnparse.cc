// dnparse: newline-JSON -> projected columnar batches.
//
// The native half of the ingest path.  The reference's hot loop parsed
// every record into a V8 object and walked it per stage
// (lib/format-json.js, vstream-json-parser); here a single streaming
// pass over the byte buffer extracts only the projected field paths and
// emits columnar arrays (value tags, numbers, interned string codes,
// pre-parsed ISO-8601 dates) that the Python/JAX engine consumes
// directly.
//
// Semantics preserved exactly:
//  * jsprim-pluck projection: a literal key "req.method" beats the
//    nested req -> method path (direct-key-first), and within the same
//    priority the *last* JSON occurrence wins (JSON.parse duplicate-key
//    rule),
//  * invalid lines are counted and skipped (vstream "invalid json"),
//  * numbers are IEEE doubles (JS semantics),
//  * ISO-8601 date parsing with ES5 rules (missing offset == UTC),
//    numbers pass through as epoch seconds (lib/stream-synthetic.js).
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <cmath>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

// ---------------------------------------------------------------------
// value tags (must match dragnet_tpu/native.py)
enum Tag : uint8_t {
  TAG_MISSING = 0,
  TAG_NULL = 1,
  TAG_FALSE = 2,
  TAG_TRUE = 3,
  TAG_NUMBER = 4,   // non-integral or large
  TAG_INT = 5,      // integral, |v| <= 2^53
  TAG_STRING = 6,
  TAG_OBJECT = 7,   // object (kept opaque: String(v) == "[object Object]")
  TAG_ARRAY = 8,    // array: raw JSON text interned for JS coercion
};

enum DateErr : uint8_t {
  DATE_OK = 0,
  DATE_UNDEF = 1,
  DATE_BAD = 2,
};

// Open-addressing interning dictionary keyed by byte span: the hot
// path (per projected string per record) never constructs a temporary
// std::string or runs std::hash — FNV over the raw span, linear probe,
// memcmp against the stored value.
struct StringDict {
  std::vector<std::string> values;
  std::vector<int32_t> table = std::vector<int32_t>(64, -1);
  size_t mask = 63;

  static uint64_t hash_span(const char* s, size_t len) {
    uint64_t h = 1469598103934665603ull;
    for (size_t i = 0; i < len; i++) {
      h ^= static_cast<unsigned char>(s[i]);
      h *= 1099511628211ull;
    }
    return h;
  }

  void grow() {
    size_t nsize = table.size() * 2;
    std::vector<int32_t> ntable(nsize, -1);
    size_t nmask = nsize - 1;
    for (int32_t c = 0; c < static_cast<int32_t>(values.size()); c++) {
      size_t i = hash_span(values[c].data(), values[c].size()) & nmask;
      while (ntable[i] != -1) i = (i + 1) & nmask;
      ntable[i] = c;
    }
    table.swap(ntable);
    mask = nmask;
  }

  int32_t code_span(const char* s, size_t len) {
    size_t i = hash_span(s, len) & mask;
    while (table[i] != -1) {
      const std::string& v = values[table[i]];
      if (v.size() == len && memcmp(v.data(), s, len) == 0)
        return table[i];
      i = (i + 1) & mask;
    }
    int32_t c = static_cast<int32_t>(values.size());
    values.emplace_back(s, len);
    table[i] = c;
    if (values.size() * 4 > table.size() * 3) grow();
    return c;
  }

  int32_t code(const std::string& s) {
    return code_span(s.data(), s.size());
  }
};

// one field's columns of one batch: what a detached batch takes with it
struct Columns {
  std::vector<uint8_t> tags;
  std::vector<double> nums;
  std::vector<int32_t> strcodes;
  std::vector<double> datesecs;   // only filled when date_hint
  std::vector<uint8_t> dateerr;   // only filled when date_hint

  void swap(Columns& o) {
    tags.swap(o.tags);
    nums.swap(o.nums);
    strcodes.swap(o.strcodes);
    datesecs.swap(o.datesecs);
    dateerr.swap(o.dateerr);
  }
  void clear() {
    tags.clear();
    nums.clear();
    strcodes.clear();
    datesecs.clear();
    dateerr.clear();
  }
};

struct FieldOut : Columns {
  StringDict dict;
  bool date_hint = false;
  bool want_dict = true;
  // scratch per record: priority of the value currently held
  // (0 = none, 1 = nested match, 2 = direct full-key match)
  uint8_t cur_prio = 0;
};

// projection trie node: at each object depth, a key either terminates a
// field (direct or final segment) or descends.  Children are a small
// linear-scan vector: record keys are matched by raw byte span with no
// hashing or allocation (projected key sets are tiny).
struct TrieNode {
  std::vector<std::pair<std::string, TrieNode*>> children;
  // field index terminated by this key at this level, with priority
  int32_t field = -1;
  uint8_t prio = 0;
  // every (field, priority) reachable at-or-below this node: used to
  // honor JSON.parse last-occurrence-wins when a later duplicate key
  // replaces a whole subtree (earlier captures must be cleared)
  std::vector<std::pair<int32_t, uint8_t>> subtree_fields;
  // first-byte dispatch: most record keys are not projected, and a
  // single table load rejects them without touching the child list
  // (-1 = no child starts with this byte, -2 = several do: scan,
  // >= 0 = the only candidate child).  Built by fill_subtree_fields.
  int16_t first_map[256];

  TrieNode() { memset(first_map, -1, sizeof(first_map)); }

  TrieNode* find(const char* k, size_t len) const {
    if (len == 0) return find_scan(k, len);  // empty projected key
    int16_t fm = first_map[static_cast<unsigned char>(k[0])];
    if (fm == -1) return nullptr;
    if (fm >= 0) {
      const auto& kv = children[fm];
      if (kv.first.size() == len &&
          memcmp(kv.first.data(), k, len) == 0) {
        return kv.second;
      }
      return nullptr;
    }
    return find_scan(k, len);
  }
  TrieNode* find_scan(const char* k, size_t len) const {
    for (const auto& kv : children) {
      if (kv.first.size() == len &&
          memcmp(kv.first.data(), k, len) == 0) {
        return kv.second;
      }
    }
    return nullptr;
  }
  TrieNode* find_or_add(const std::string& k) {
    TrieNode* n = find_scan(k.data(), k.size());
    if (n != nullptr) return n;
    n = new TrieNode();
    children.emplace_back(k, n);
    return n;
  }
  void build_first_map() {
    memset(first_map, -1, sizeof(first_map));
    for (size_t i = 0; i < children.size(); i++) {
      if (children[i].first.empty()) continue;
      unsigned char b =
          static_cast<unsigned char>(children[i].first[0]);
      first_map[b] = first_map[b] == -1 ? static_cast<int16_t>(i) : -2;
    }
  }
  ~TrieNode() {
    for (auto& kv : children) delete kv.second;
  }
};

struct Parser {
  std::vector<std::string> paths;
  std::vector<FieldOut> fields;
  TrieNode root;
  // shared read-only projection trie (workers point at the main
  // parser's root; the owner points at its own)
  const TrieNode* trie = nullptr;
  uint64_t nlines = 0;
  uint64_t nbad = 0;
  uint64_t nrecords = 0;
  uint64_t batch_records = 0;
  std::string err;
  // worker pool for multithreaded parse (owner only)
  std::vector<Parser*> workers;
  // persistent worker-code -> owner-code dictionary remaps,
  // [worker][field][worker_code]
  std::vector<std::vector<std::vector<int32_t>>> remaps;
  // cleared column sets of released batches, kept for their capacity:
  // detach takes one in place of fresh memory, so a stream of batches
  // stops allocating (and page-faulting) after its first few.  A batch
  // is released on its consumer's thread while the owner parses, hence
  // the lock.
  std::mutex spare_mu;
  std::vector<std::vector<Columns>> spares;

  ~Parser() {
    for (Parser* w : workers) delete w;
  }
};

// ---------------------------------------------------------------------
// date parsing: ISO-8601 subset (ES5 Date.parse), returns ms since
// epoch; false on failure.
bool days_from_civil(int64_t y, unsigned m, unsigned d, int64_t* out) {
  // Howard Hinnant's algorithm
  y -= m <= 2;
  const int64_t era = (y >= 0 ? y : y - 399) / 400;
  const unsigned yoe = static_cast<unsigned>(y - era * 400);
  const unsigned doy = (153 * (m + (m > 2 ? -3 : 9)) + 2) / 5 + d - 1;
  const unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  *out = era * 146097 + static_cast<int64_t>(doe) - 719468;
  return true;
}

inline bool two_digits(const char* p, int* out) {
  if (p[0] < '0' || p[0] > '9' || p[1] < '0' || p[1] > '9') return false;
  *out = (p[0] - '0') * 10 + (p[1] - '0');
  return true;
}

bool parse_iso_date(const char* s, size_t len, int64_t* ms_out) {
  // YYYY[-MM[-DD]][T HH:MM[:SS[.fff...]][Z|+-HH:MM|+-HHMM]]
  // The Python reference (jsvalues.date_parse) strips surrounding
  // whitespace before matching; mirror it so both parse lanes agree.
  while (len > 0 && (*s == ' ' || *s == '\t' || *s == '\r' ||
                     *s == '\n' || *s == '\f' || *s == '\v')) {
    s++;
    len--;
  }
  while (len > 0 && (s[len - 1] == ' ' || s[len - 1] == '\t' ||
                     s[len - 1] == '\r' || s[len - 1] == '\n' ||
                     s[len - 1] == '\f' || s[len - 1] == '\v')) {
    len--;
  }
  if (len < 4) return false;
  const char* p = s;
  const char* end = s + len;
  int year = 0;
  for (int i = 0; i < 4; i++) {
    if (p[i] < '0' || p[i] > '9') return false;
    year = year * 10 + (p[i] - '0');
  }
  p += 4;
  int month = 1, day = 1, hh = 0, mm = 0, ss = 0, msec = 0;
  if (p < end && *p == '-') {
    if (end - p < 3 || !two_digits(p + 1, &month)) return false;
    p += 3;
    if (p < end && *p == '-') {
      if (end - p < 3 || !two_digits(p + 1, &day)) return false;
      p += 3;
    }
  }
  long tz_offset_min = 0;
  if (p < end) {
    if (*p != 'T' && *p != ' ') return false;
    p++;
    if (end - p < 5 || !two_digits(p, &hh)) return false;
    if (p[2] != ':') return false;
    if (!two_digits(p + 3, &mm)) return false;
    p += 5;
    if (p < end && *p == ':') {
      if (end - p < 3 || !two_digits(p + 1, &ss)) return false;
      p += 3;
      if (p < end && *p == '.') {
        p++;
        int ndig = 0;
        int frac = 0;
        while (p < end && *p >= '0' && *p <= '9') {
          if (ndig < 3) frac = frac * 10 + (*p - '0');
          ndig++;
          p++;
        }
        if (ndig == 0) return false;
        while (ndig < 3) { frac *= 10; ndig++; }
        msec = frac;
      }
    }
    if (p < end) {
      if (*p == 'Z') {
        p++;
      } else if (*p == '+' || *p == '-') {
        // offsets require minutes: [+-]HH:MM or [+-]HHMM
        // (matching the reference path's ISO regex)
        int sign = (*p == '+') ? 1 : -1;
        p++;
        int tzh = 0, tzm = 0;
        if (end - p < 2 || !two_digits(p, &tzh)) return false;
        p += 2;
        if (p < end && *p == ':') p++;
        if (end - p < 2 || !two_digits(p, &tzm)) return false;
        p += 2;
        tz_offset_min = sign * (tzh * 60 + tzm);
      } else {
        return false;
      }
    }
  }
  if (p != end) return false;
  // the Python reference path builds a datetime, which rejects year 0
  if (year < 1) return false;
  if (month < 1 || month > 12) return false;
  static const int kDays[] = {31, 28, 31, 30, 31, 30,
                              31, 31, 30, 31, 30, 31};
  int maxday = kDays[month - 1];
  if (month == 2 &&
      (year % 4 == 0 && (year % 100 != 0 || year % 400 == 0))) {
    maxday = 29;
  }
  if (day < 1 || day > maxday) return false;
  // the Python reference path builds a datetime, which rejects hour 24
  if (hh > 23 || mm > 59 || ss > 59) return false;
  int64_t days;
  days_from_civil(year, month, day, &days);
  int64_t ms = ((days * 24 + hh) * 60 + mm) * 60 + ss;
  ms = ms * 1000 + msec;
  ms -= tz_offset_min * 60000;
  *ms_out = ms;
  return true;
}

// ---------------------------------------------------------------------
// JSON scanning

// Scan: advance to the first byte that is '"', '\\', or a raw
// control char (< 0x20).  These are the only bytes a JSON string
// scanner must act on; everything else is literal content.  SWAR
// (8 bytes/step) baseline with an AVX2 (32 bytes/step) variant
// dispatched at runtime — the library is built on the host it runs
// on, but the binary stays loadable on machines without AVX2.
static inline const char* scan_plain_swar(const char* p,
                                          const char* end) {
  constexpr uint64_t kOnes = 0x0101010101010101ull;
  constexpr uint64_t kHigh = 0x8080808080808080ull;
  while (end - p >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    uint64_t q = w ^ (kOnes * 0x22);          // '"'
    uint64_t b = w ^ (kOnes * 0x5C);          // '\\'
    uint64_t c = w & (kOnes * 0xE0);          // 0 iff byte < 0x20
    uint64_t hit = ((q - kOnes) & ~q & kHigh) |
                   ((b - kOnes) & ~b & kHigh) |
                   ((c - kOnes) & ~c & kHigh);
    if (hit)
      return p + (__builtin_ctzll(hit) >> 3);
    p += 8;
  }
  while (p < end) {
    unsigned char ch = static_cast<unsigned char>(*p);
    if (ch == '"' || ch == '\\' || ch < 0x20)
      return p;
    p++;
  }
  return end;
}

#if defined(__x86_64__)
#include <immintrin.h>
__attribute__((target("avx2")))
static const char* scan_plain_avx2(const char* p, const char* end) {
  const __m256i vq = _mm256_set1_epi8('"');
  const __m256i vb = _mm256_set1_epi8('\\');
  const __m256i vlim = _mm256_set1_epi8(0x1F);
  while (end - p >= 32) {
    __m256i w = _mm256_loadu_si256(
        reinterpret_cast<const __m256i*>(p));
    __m256i hq = _mm256_cmpeq_epi8(w, vq);
    __m256i hb = _mm256_cmpeq_epi8(w, vb);
    // unsigned (byte < 0x20)  <=>  min(byte, 0x1F) == byte
    __m256i hc = _mm256_cmpeq_epi8(_mm256_min_epu8(w, vlim), w);
    unsigned mask = static_cast<unsigned>(_mm256_movemask_epi8(
        _mm256_or_si256(hq, _mm256_or_si256(hb, hc))));
    if (mask)
      return p + __builtin_ctz(mask);
    p += 32;
  }
  return scan_plain_swar(p, end);
}

static const bool kHaveAvx2 =
    (__builtin_cpu_init(), __builtin_cpu_supports("avx2"));

static inline const char* scan_plain(const char* p, const char* end) {
  if (kHaveAvx2)
    return scan_plain_avx2(p, end);
  return scan_plain_swar(p, end);
}
#else
static inline const char* scan_plain(const char* p, const char* end) {
  return scan_plain_swar(p, end);
}
#endif

struct Scanner {
  const char* p;
  const char* end;

  bool at_end() const { return p >= end; }
  char peek() const { return *p; }

  void skip_ws() {
    while (p < end &&
           (*p == ' ' || *p == '\t' || *p == '\r' || *p == '\n')) p++;
  }

  bool skip_string() {
    // assumes *p == '"'; validates JSON string syntax (escape set,
    // no raw control chars) so the skip path rejects exactly what
    // JSON.parse / json.loads reject
    p++;
    while (true) {
      p = scan_plain(p, end);
      if (p >= end) return false;
      unsigned char c = static_cast<unsigned char>(*p);
      if (c == '"') {
        p++;
        return true;
      }
      if (c < 0x20) return false;
      // backslash escape
      p++;
      if (p >= end) return false;
      char e = *p;
      if (e == 'u') {
        if (end - p < 5) return false;
        for (int i = 1; i <= 4; i++) {
          char h = p[i];
          if (!((h >= '0' && h <= '9') || (h >= 'a' && h <= 'f') ||
                (h >= 'A' && h <= 'F'))) return false;
        }
        p += 5;
      } else if (e == '"' || e == '\\' || e == '/' || e == 'b' ||
                 e == 'f' || e == 'n' || e == 'r' || e == 't') {
        p++;
      } else {
        return false;
      }
    }
  }

  // Scan a JSON string assuming *p == '"'.  Fast path: no escapes and
  // no raw control chars -> returns the raw byte span (still valid
  // UTF-8 text, since JSON strings without escapes are literal).  If an
  // escape is present, falls back to full decode into *decoded and sets
  // *span_len = SIZE_MAX.  Returns false on invalid string syntax.
  bool read_string_span(const char** span, size_t* span_len,
                        std::string* decoded) {
    const char* q = scan_plain(p + 1, end);
    if (q >= end) return false;
    if (*q == '"') {
      *span = p + 1;
      *span_len = static_cast<size_t>(q - (p + 1));
      p = q + 1;
      return true;
    }
    if (static_cast<unsigned char>(*q) < 0x20) return false;
    *span_len = static_cast<size_t>(-1);
    return read_string(decoded);
  }

  // decode a JSON string into out (UTF-8); assumes *p == '"'
  bool read_string(std::string* out) {
    p++;
    out->clear();
    while (p < end) {
      unsigned char c = static_cast<unsigned char>(*p);
      if (c == '"') {
        p++;
        return true;
      }
      if (c == '\\') {
        p++;
        if (p >= end) return false;
        char e = *p++;
        switch (e) {
          case '"': out->push_back('"'); break;
          case '\\': out->push_back('\\'); break;
          case '/': out->push_back('/'); break;
          case 'b': out->push_back('\b'); break;
          case 'f': out->push_back('\f'); break;
          case 'n': out->push_back('\n'); break;
          case 'r': out->push_back('\r'); break;
          case 't': out->push_back('\t'); break;
          case 'u': {
            if (end - p < 4) return false;
            unsigned cp = 0;
            for (int i = 0; i < 4; i++) {
              char h = p[i];
              cp <<= 4;
              if (h >= '0' && h <= '9') cp |= h - '0';
              else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
              else return false;
            }
            p += 4;
            if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 &&
                p[0] == '\\' && p[1] == 'u') {
              unsigned lo = 0;
              bool ok = true;
              for (int i = 0; i < 4; i++) {
                char h = p[2 + i];
                lo <<= 4;
                if (h >= '0' && h <= '9') lo |= h - '0';
                else if (h >= 'a' && h <= 'f') lo |= h - 'a' + 10;
                else if (h >= 'A' && h <= 'F') lo |= h - 'A' + 10;
                else { ok = false; break; }
              }
              if (ok && lo >= 0xDC00 && lo <= 0xDFFF) {
                cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
                p += 6;
              }
            }
            // encode UTF-8
            if (cp < 0x80) {
              out->push_back(static_cast<char>(cp));
            } else if (cp < 0x800) {
              out->push_back(static_cast<char>(0xC0 | (cp >> 6)));
              out->push_back(static_cast<char>(0x80 | (cp & 0x3F)));
            } else if (cp < 0x10000) {
              out->push_back(static_cast<char>(0xE0 | (cp >> 12)));
              out->push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
              out->push_back(static_cast<char>(0x80 | (cp & 0x3F)));
            } else {
              out->push_back(static_cast<char>(0xF0 | (cp >> 18)));
              out->push_back(static_cast<char>(0x80 | ((cp >> 12) & 0x3F)));
              out->push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
              out->push_back(static_cast<char>(0x80 | (cp & 0x3F)));
            }
            break;
          }
          default:
            return false;
        }
      } else if (c < 0x20) {
        return false;
      } else {
        out->push_back(static_cast<char>(c));
        p++;
      }
    }
    return false;
  }

  // skip any JSON value, validating full JSON grammar so the native
  // path rejects exactly the lines the Python fallback rejects
  bool skip_value() {
    skip_ws();
    if (at_end()) return false;
    char c = *p;
    if (c == '"') return skip_string();
    if (c == '{') return skip_object_strict();
    if (c == '[') return skip_array_strict();
    if (c == 't') return literal("true");
    if (c == 'f') return literal("false");
    if (c == 'n') return literal("null");
    return skip_number(nullptr, nullptr);
  }

  bool skip_object_strict() {
    p++;  // '{'
    skip_ws();
    if (!at_end() && *p == '}') { p++; return true; }
    while (true) {
      skip_ws();
      if (at_end() || *p != '"') return false;
      if (!skip_string()) return false;
      skip_ws();
      if (at_end() || *p != ':') return false;
      p++;
      if (!skip_value()) return false;
      skip_ws();
      if (at_end()) return false;
      if (*p == ',') { p++; continue; }
      if (*p == '}') { p++; return true; }
      return false;
    }
  }

  bool skip_array_strict() {
    p++;  // '['
    skip_ws();
    if (!at_end() && *p == ']') { p++; return true; }
    while (true) {
      if (!skip_value()) return false;
      skip_ws();
      if (at_end()) return false;
      if (*p == ',') { p++; continue; }
      if (*p == ']') { p++; return true; }
      return false;
    }
  }

  bool literal(const char* lit) {
    size_t len = strlen(lit);
    if (static_cast<size_t>(end - p) < len ||
        memcmp(p, lit, len) != 0) return false;
    p += len;
    return true;
  }

  bool skip_number(double* out, bool* is_int) {
    // strict JSON number grammar: -?(0|[1-9][0-9]*)(\.[0-9]+)?
    // ([eE][+-]?[0-9]+)?  (no leading zeros, no bare "1.")
    const char* start = p;
    bool neg = false;
    if (p < end && (*p == '-')) { neg = true; p++; }
    if (p >= end || *p < '0' || *p > '9') return false;
    uint64_t mant = 0;
    int ndigits = 0;
    if (*p == '0') {
      p++;
      ndigits = 1;
    } else {
      while (p < end && *p >= '0' && *p <= '9') {
        if (ndigits < 19) mant = mant * 10 + (*p - '0');
        ndigits++;
        p++;
      }
    }
    bool integral = true;
    if (p < end && *p == '.') {
      integral = false;
      p++;
      if (p >= end || *p < '0' || *p > '9') return false;
      while (p < end && *p >= '0' && *p <= '9') p++;
    }
    if (p < end && (*p == 'e' || *p == 'E')) {
      integral = false;
      p++;
      if (p < end && (*p == '+' || *p == '-')) p++;
      if (p >= end || *p < '0' || *p > '9') return false;
      while (p < end && *p >= '0' && *p <= '9') p++;
    }
    if (out != nullptr) {
      if (integral && ndigits <= 18) {
        // <= 18 digits fits uint64 exactly; uint64 -> double rounds to
        // nearest, matching strtod's correctly-rounded result
        double v = static_cast<double>(mant);
        *out = neg ? -v : v;
        *is_int = std::fabs(*out) <= 9007199254740992.0;
      } else {
        char tmp[512];
        size_t n = static_cast<size_t>(p - start);
        if (n >= sizeof(tmp)) {
          std::string big(start, n);
          *out = strtod(big.c_str(), nullptr);
        } else {
          memcpy(tmp, start, n);
          tmp[n] = '\0';
          *out = strtod(tmp, nullptr);
        }
        double v = *out;
        *is_int = integral && std::fabs(v) <= 9007199254740992.0 &&
                  v == std::floor(v);
      }
    }
    return true;
  }
};

// parse one record line, filling matched fields
bool parse_object(Parser* pr, Scanner* sc, const TrieNode* node,
                  int depth) {
  sc->skip_ws();
  if (sc->at_end() || sc->peek() != '{') return false;
  sc->p++;
  sc->skip_ws();
  if (!sc->at_end() && sc->peek() == '}') { sc->p++; return true; }

  std::string key;
  std::string sval;
  while (true) {
    sc->skip_ws();
    if (sc->at_end() || sc->peek() != '"') return false;
    const char* kspan;
    size_t klen;
    if (!sc->read_string_span(&kspan, &klen, &key)) return false;
    if (klen == static_cast<size_t>(-1)) {
      kspan = key.data();
      klen = key.size();
    }
    sc->skip_ws();
    if (sc->at_end() || sc->peek() != ':') return false;
    sc->p++;
    sc->skip_ws();

    const TrieNode* child =
        (node != nullptr) ? node->find(kspan, klen) : nullptr;

    if (child != nullptr) {
      // JSON.parse keeps the LAST occurrence of a duplicate key: any
      // field previously captured through this key's subtree (at the
      // priority this subtree grants) must be cleared before the new
      // value is considered — even if the new value is a non-object
      // that provides nothing.
      for (const auto& fp : child->subtree_fields) {
        FieldOut& f = pr->fields[fp.first];
        if (f.cur_prio != 0 && f.cur_prio <= fp.second) {
          size_t i = f.tags.size() - 1;
          f.cur_prio = 0;
          f.tags[i] = TAG_MISSING;
          f.nums[i] = 0.0;
          f.strcodes[i] = -1;
          if (f.date_hint) {
            f.datesecs[i] = 0.0;
            f.dateerr[i] = DATE_UNDEF;
          }
        }
      }
    }

    if (child != nullptr && child->field >= 0) {
      FieldOut& f = pr->fields[child->field];
      // direct-key-first: a higher-priority match overwrites a lower
      // one; same priority -> last occurrence wins (JSON.parse rule)
      if (child->prio >= f.cur_prio) {
        f.cur_prio = child->prio;
        size_t i = f.tags.size() - 1;  // current record slot
        char c = sc->at_end() ? '\0' : sc->peek();
        if (c == '"') {
          const char* vspan;
          size_t vlen;
          if (!sc->read_string_span(&vspan, &vlen, &sval)) return false;
          if (vlen == static_cast<size_t>(-1)) {
            vspan = sval.data();
            vlen = sval.size();
          }
          f.tags[i] = TAG_STRING;
          f.strcodes[i] = f.want_dict
              ? f.dict.code_span(vspan, vlen) : -1;
          if (f.date_hint) {
            int64_t ms;
            if (parse_iso_date(vspan, vlen, &ms)) {
              f.dateerr[i] = DATE_OK;
              // JS Math.floor(ms/1000)
              double d = static_cast<double>(ms);
              f.datesecs[i] = std::floor(d / 1000.0);
            } else {
              f.dateerr[i] = DATE_BAD;
            }
          }
        } else if (c == '[') {
          // arrays participate in JS coercion (String/Number via
          // join), so intern the raw JSON text for host-side handling
          const char* vstart = sc->p;
          if (!sc->skip_value()) return false;
          f.tags[i] = TAG_ARRAY;
          f.strcodes[i] = f.want_dict
              ? f.dict.code_span(vstart,
                                 static_cast<size_t>(sc->p - vstart))
              : -1;
          if (f.date_hint) f.dateerr[i] = DATE_BAD;
        } else if (c == '{') {
          if (child->children.empty()) {
            if (!sc->skip_value()) return false;
            f.tags[i] = TAG_OBJECT;
            if (f.date_hint) f.dateerr[i] = DATE_BAD;
          } else {
            // rare: key both terminates one field and prefixes others
            if (!parse_object(pr, sc, child, depth + 1)) return false;
            f.tags[i] = TAG_OBJECT;
            if (f.date_hint) f.dateerr[i] = DATE_BAD;
          }
        } else if (c == 't' || c == 'f') {
          bool istrue = (c == 't');
          if (!sc->literal(istrue ? "true" : "false")) return false;
          f.tags[i] = istrue ? TAG_TRUE : TAG_FALSE;
          if (f.date_hint) f.dateerr[i] = DATE_BAD;
        } else if (c == 'n') {
          if (!sc->literal("null")) return false;
          f.tags[i] = TAG_NULL;
          if (f.date_hint) f.dateerr[i] = DATE_BAD;
        } else {
          double num;
          bool is_int;
          if (!sc->skip_number(&num, &is_int)) return false;
          f.tags[i] = is_int ? TAG_INT : TAG_NUMBER;
          f.nums[i] = num;
          if (f.date_hint) {
            // numbers pass through as already-parsed epoch seconds
            f.dateerr[i] = DATE_OK;
            f.datesecs[i] = num;
          }
        }
        goto next_member;
      }
    }

    if (child != nullptr && !child->children.empty() &&
        !sc->at_end() && sc->peek() == '{') {
      if (!parse_object(pr, sc, child, depth + 1)) return false;
    } else {
      if (!sc->skip_value()) return false;
    }

  next_member:
    sc->skip_ws();
    if (sc->at_end()) return false;
    if (sc->peek() == ',') {
      sc->p++;
      continue;
    }
    if (sc->peek() == '}') {
      sc->p++;
      return true;
    }
    return false;
  }
}

void fill_subtree_fields(TrieNode* node);

void build_trie(Parser* pr) {
  // jsprim-pluck lookup order: at every object level the literal
  // remaining path is checked before splitting on the first dot, so a
  // match's priority decreases with the number of splits taken
  // (255 = fully direct).  Higher priority overwrites lower; equal
  // priority keeps the last JSON occurrence (JSON.parse rule).
  for (size_t fi = 0; fi < pr->paths.size(); fi++) {
    const std::string& path = pr->paths[fi];
    struct Item { TrieNode* node; std::string rest; uint8_t splits; };
    std::vector<Item> frontier;
    frontier.push_back({&pr->root, path, 0});
    while (!frontier.empty()) {
      Item item = frontier.back();
      frontier.pop_back();
      // the full remaining path is a direct key at this level
      TrieNode* leaf = item.node->find_or_add(item.rest);
      uint8_t prio = static_cast<uint8_t>(255 - item.splits);
      if (leaf->field < 0 || prio > leaf->prio) {
        leaf->field = static_cast<int32_t>(fi);
        leaf->prio = prio;
      }
      size_t dot = item.rest.find('.');
      if (dot == std::string::npos) continue;
      std::string head = item.rest.substr(0, dot);
      std::string tail = item.rest.substr(dot + 1);
      TrieNode* sub = item.node->find_or_add(head);
      frontier.push_back({sub, tail,
                          static_cast<uint8_t>(item.splits + 1)});
    }
  }
  fill_subtree_fields(&pr->root);
}

void fill_subtree_fields(TrieNode* node) {
  node->build_first_map();
  if (node->field >= 0) {
    node->subtree_fields.emplace_back(node->field, node->prio);
  }
  for (auto& kv : node->children) {
    fill_subtree_fields(kv.second);
    for (const auto& fp : kv.second->subtree_fields) {
      node->subtree_fields.push_back(fp);
    }
  }
}

}  // namespace

extern "C" {

void* dn_parser_create(const char** paths, const uint8_t* date_hints,
                       int32_t nfields) {
  Parser* pr = new Parser();
  pr->fields.resize(nfields);
  for (int32_t i = 0; i < nfields; i++) {
    pr->paths.emplace_back(paths[i]);
    pr->fields[i].date_hint = date_hints[i] != 0;
  }
  build_trie(pr);
  pr->trie = &pr->root;
  return pr;
}

// Variant with per-field dictionary control: want_dict[i] == 0 means
// the engine never reads this field's string dictionary (date-only
// sources, consumed via the pre-parsed date columns) — string/array
// values then skip interning entirely (strcode -1), which for
// timestamp-like fields saves a hash + heap string per record.
void* dn_parser_create2(const char** paths, const uint8_t* date_hints,
                        const uint8_t* want_dict, int32_t nfields) {
  Parser* pr = static_cast<Parser*>(
      dn_parser_create(paths, date_hints, nfields));
  for (int32_t i = 0; i < nfields; i++)
    pr->fields[i].want_dict = want_dict[i] != 0;
  return pr;
}

void dn_parser_destroy(void* h) {
  delete static_cast<Parser*>(h);
}

// Parse a buffer of newline-separated JSON.  Appends one slot per valid
// record to every field's output arrays.  Returns the number of records
// appended in this call.
int64_t dn_parser_parse(void* h, const char* buf, int64_t len) {
  Parser* pr = static_cast<Parser*>(h);
  const char* p = buf;
  const char* end = buf + len;
  int64_t appended = 0;

  while (p < end) {
    const char* nl = static_cast<const char*>(
        memchr(p, '\n', end - p));
    const char* line_end = (nl != nullptr) ? nl : end;
    pr->nlines++;

    // provision a slot in every field
    for (auto& f : pr->fields) {
      f.tags.push_back(TAG_MISSING);
      f.nums.push_back(0.0);
      f.strcodes.push_back(-1);
      if (f.date_hint) {
        f.datesecs.push_back(0.0);
        f.dateerr.push_back(DATE_UNDEF);
      }
      f.cur_prio = 0;
    }

    Scanner sc{p, line_end};
    sc.skip_ws();
    bool ok;
    if (!sc.at_end() && sc.peek() == '{') {
      ok = parse_object(pr, &sc, pr->trie, 0);
    } else {
      // any valid JSON value is a record (JSON.parse-per-line
      // semantics); projected fields simply stay missing
      ok = !sc.at_end() && sc.skip_value();
    }
    if (ok) {
      sc.skip_ws();
      ok = sc.at_end();
    }
    if (!ok) {
      // roll back the slot
      for (auto& f : pr->fields) {
        f.tags.pop_back();
        f.nums.pop_back();
        f.strcodes.pop_back();
        if (f.date_hint) {
          f.datesecs.pop_back();
          f.dateerr.pop_back();
        }
      }
      pr->nbad++;
    } else {
      pr->nrecords++;
      pr->batch_records++;
      appended++;
    }

    if (nl == nullptr) break;
    p = nl + 1;
  }
  return appended;
}

void dn_parser_reset_batch(void* h);

// Multithreaded parse: splits the buffer at newline boundaries into
// nthreads chunks, parses each on a worker with its own field outputs
// and dictionaries, then appends worker results to the owner in chunk
// order.  Record order, counters, and dictionary-code assignment order
// are bit-identical to the single-threaded path: chunks merge in input
// order, and each worker's new dictionary entries (first-occurrence
// order within the chunk) are interned into the owner dictionary before
// any later chunk's.
int64_t dn_parser_parse_mt(void* h, const char* buf, int64_t len,
                           int32_t nthreads) {
  Parser* pr = static_cast<Parser*>(h);
  if (nthreads < 1) nthreads = 1;
  // small buffers: threading overhead dominates
  if (nthreads == 1 || len < (1 << 21)) {
    return dn_parser_parse(h, buf, len);
  }

  // chunk boundaries on newlines
  std::vector<std::pair<const char*, const char*>> chunks;
  const char* pos = buf;
  const char* end = buf + len;
  for (int32_t t = 0; t < nthreads && pos < end; t++) {
    const char* target = buf + (len * (t + 1)) / nthreads;
    if (t == nthreads - 1 || target >= end) {
      chunks.emplace_back(pos, end);
      pos = end;
      break;
    }
    const char* nl = static_cast<const char*>(
        memchr(target, '\n', end - target));
    const char* cend = (nl != nullptr) ? nl + 1 : end;
    if (cend > pos) chunks.emplace_back(pos, cend);
    pos = cend;
  }
  if (chunks.size() <= 1) return dn_parser_parse(h, buf, len);

  // lazily grow the persistent worker pool
  while (pr->workers.size() < chunks.size()) {
    Parser* w = new Parser();
    w->fields.resize(pr->fields.size());
    for (size_t i = 0; i < pr->fields.size(); i++) {
      w->fields[i].date_hint = pr->fields[i].date_hint;
      w->fields[i].want_dict = pr->fields[i].want_dict;
    }
    w->trie = &pr->root;
    pr->workers.push_back(w);
    pr->remaps.emplace_back(
        std::vector<std::vector<int32_t>>(pr->fields.size()));
  }

  std::vector<std::thread> threads;
  size_t spawned = 0;
  try {
    for (size_t t = 0; t < chunks.size(); t++) {
      Parser* w = pr->workers[t];
      const char* cbeg = chunks[t].first;
      const char* cend = chunks[t].second;
      threads.emplace_back([w, cbeg, cend]() {
        dn_parser_reset_batch(w);
        dn_parser_parse(w, cbeg,
                        static_cast<int64_t>(cend - cbeg));
      });
      spawned++;
    }
  } catch (...) {
    // thread creation failed (cgroup pid limit, EAGAIN): join what
    // started, run the rest inline, and merge as usual
    for (auto& th : threads) th.join();
    for (size_t t = spawned; t < chunks.size(); t++) {
      Parser* w = pr->workers[t];
      dn_parser_reset_batch(w);
      dn_parser_parse(w, chunks[t].first,
                      static_cast<int64_t>(
                          chunks[t].second - chunks[t].first));
    }
    threads.clear();
  }
  for (auto& th : threads) th.join();

  // ordered merge
  int64_t total = 0;
  for (size_t t = 0; t < chunks.size(); t++) {
    Parser* w = pr->workers[t];
    int64_t n = static_cast<int64_t>(w->batch_records);
    pr->nlines += w->nlines;
    pr->nbad += w->nbad;
    w->nlines = 0;
    w->nbad = 0;
    w->nrecords = 0;
    pr->nrecords += static_cast<uint64_t>(n);
    pr->batch_records += static_cast<uint64_t>(n);
    total += n;
    for (size_t fi = 0; fi < pr->fields.size(); fi++) {
      FieldOut& dst = pr->fields[fi];
      FieldOut& src = w->fields[fi];
      // extend the persistent code remap for this worker's new strings
      std::vector<int32_t>& remap = pr->remaps[t][fi];
      for (size_t c = remap.size(); c < src.dict.values.size(); c++) {
        remap.push_back(dst.dict.code(src.dict.values[c]));
      }
      dst.tags.insert(dst.tags.end(), src.tags.begin(), src.tags.end());
      dst.nums.insert(dst.nums.end(), src.nums.begin(), src.nums.end());
      size_t base = dst.strcodes.size();
      dst.strcodes.insert(dst.strcodes.end(), src.strcodes.begin(),
                          src.strcodes.end());
      for (size_t i = base; i < dst.strcodes.size(); i++) {
        int32_t c = dst.strcodes[i];
        if (c >= 0) dst.strcodes[i] = remap[c];
      }
      if (dst.date_hint) {
        dst.datesecs.insert(dst.datesecs.end(), src.datesecs.begin(),
                            src.datesecs.end());
        dst.dateerr.insert(dst.dateerr.end(), src.dateerr.begin(),
                           src.dateerr.end());
      }
    }
  }
  return total;
}

int64_t dn_parser_nlines(void* h) {
  return static_cast<Parser*>(h)->nlines;
}
int64_t dn_parser_nbad(void* h) {
  return static_cast<Parser*>(h)->nbad;
}

int64_t dn_parser_batch_size(void* h) {
  return static_cast<int64_t>(
      static_cast<Parser*>(h)->batch_records);
}

const uint8_t* dn_parser_tags(void* h, int32_t field) {
  return static_cast<Parser*>(h)->fields[field].tags.data();
}
const double* dn_parser_nums(void* h, int32_t field) {
  return static_cast<Parser*>(h)->fields[field].nums.data();
}
const int32_t* dn_parser_strcodes(void* h, int32_t field) {
  return static_cast<Parser*>(h)->fields[field].strcodes.data();
}
const double* dn_parser_datesecs(void* h, int32_t field) {
  return static_cast<Parser*>(h)->fields[field].datesecs.data();
}
const uint8_t* dn_parser_dateerr(void* h, int32_t field) {
  return static_cast<Parser*>(h)->fields[field].dateerr.data();
}

// One-pass per-field batch statistics for the device path's
// eligibility checks (replacing several numpy scans per batch):
//   out[0] = count of TAG_ARRAY rows
//   out[1] = 1 when every numeric row is a finite integer within int32
//   out[2] = numeric min (0 when no numeric rows)
//   out[3] = numeric max (0 when no numeric rows)
//   out[4] = count of numeric rows (TAG_INT | TAG_NUMBER)
//   out[5] = count of TAG_STRING rows
void dn_parser_field_stats(void* h, int32_t field, double* out) {
  Parser* pr = static_cast<Parser*>(h);
  FieldOut& f = pr->fields[field];
  size_t n = f.tags.size();
  int64_t narr = 0, nnum = 0, nstr = 0;
  int all_i32 = 1;
  double mn = 0.0, mx = 0.0;
  for (size_t i = 0; i < n; i++) {
    uint8_t t = f.tags[i];
    if (t == TAG_INT || t == TAG_NUMBER) {
      double v = f.nums[i];
      if (nnum == 0) {
        mn = mx = v;
      } else {
        if (v < mn) mn = v;
        if (v > mx) mx = v;
      }
      nnum++;
      // NaN/inf fail the comparisons, clearing the flag
      if (!(v >= -2147483648.0 && v <= 2147483647.0 &&
            v == std::floor(v))) {
        all_i32 = 0;
      }
    } else if (t == TAG_ARRAY) {
      narr++;
    } else if (t == TAG_STRING) {
      nstr++;
    }
  }
  out[0] = static_cast<double>(narr);
  out[1] = static_cast<double>(all_i32);
  out[2] = mn;
  out[3] = mx;
  out[4] = static_cast<double>(nnum);
  out[5] = static_cast<double>(nstr);
}

// Numeric rows cast to int32 (caller must have checked the all-i32
// stat); non-numeric rows are 0.
void dn_parser_nums_i32(void* h, int32_t field, int32_t* out) {
  Parser* pr = static_cast<Parser*>(h);
  FieldOut& f = pr->fields[field];
  size_t n = f.tags.size();
  for (size_t i = 0; i < n; i++) {
    uint8_t t = f.tags[i];
    out[i] = (t == TAG_INT || t == TAG_NUMBER)
                 ? static_cast<int32_t>(f.nums[i])
                 : 0;
  }
}

// Date-column stats over error-free rows:
//   out[0] = 1 when every ok row's epoch-seconds is an integer in i32
//   out[1] = count of ok rows
void dn_parser_date_stats(void* h, int32_t field, double* out) {
  Parser* pr = static_cast<Parser*>(h);
  FieldOut& f = pr->fields[field];
  size_t n = f.dateerr.size();
  int all_i32 = 1;
  int64_t nok = 0;
  for (size_t i = 0; i < n; i++) {
    if (f.dateerr[i] != 0) continue;
    nok++;
    double v = f.datesecs[i];
    if (!(v >= -2147483648.0 && v <= 2147483647.0 &&
          v == std::floor(v))) {
      all_i32 = 0;
    }
  }
  out[0] = static_cast<double>(all_i32);
  out[1] = static_cast<double>(nok);
}

// Epoch seconds as int32 (error rows 0); caller checks date_stats.
void dn_parser_date_i32(void* h, int32_t field, int32_t* out) {
  Parser* pr = static_cast<Parser*>(h);
  FieldOut& f = pr->fields[field];
  size_t n = f.dateerr.size();
  for (size_t i = 0; i < n; i++) {
    out[i] = (f.dateerr[i] == 0)
                 ? static_cast<int32_t>(f.datesecs[i])
                 : 0;
  }
}

int32_t dn_parser_dict_size(void* h, int32_t field) {
  return static_cast<int32_t>(
      static_cast<Parser*>(h)->fields[field].dict.values.size());
}
const char* dn_parser_dict_get(void* h, int32_t field, int32_t code,
                               int32_t* len) {
  const std::string& s =
      static_cast<Parser*>(h)->fields[field].dict.values[code];
  *len = static_cast<int32_t>(s.size());
  return s.data();
}

// Reset per-batch outputs (dictionaries persist across batches).
void dn_parser_reset_batch(void* h) {
  Parser* pr = static_cast<Parser*>(h);
  pr->batch_records = 0;
  for (auto& f : pr->fields) f.clear();
}

// Hand the current batch off: its columns MOVE (swapped, not copied)
// into a new handle that answers the per-batch accessors above
// (batch_size, tags/nums/strcodes/datesecs/dateerr, field_stats,
// nums_i32, date_stats, date_i32), and the parser goes on into empty
// columns, so another thread can read the batch while this one parses
// the next.  Counters and dictionaries stay with the parser: the caller
// reads the former and pins the lengths of the latter before the next
// parse.  Release the handle with dn_parser_release_batch.
void* dn_parser_detach_batch(void* h) {
  Parser* pr = static_cast<Parser*>(h);
  Parser* b = new Parser();
  b->batch_records = pr->batch_records;
  b->fields.resize(pr->fields.size());
  std::vector<Columns> spare;
  {
    std::lock_guard<std::mutex> lock(pr->spare_mu);
    if (!pr->spares.empty()) {
      spare.swap(pr->spares.back());
      pr->spares.pop_back();
    }
  }
  for (size_t i = 0; i < pr->fields.size(); i++) {
    b->fields[i].swap(pr->fields[i]);
    if (i < spare.size()) pr->fields[i].swap(spare[i]);
  }
  pr->batch_records = 0;
  return b;
}

// Free a detached batch; its columns' memory goes back to the parser
// it came from (h, which the caller keeps alive; null: just free).
void dn_parser_release_batch(void* h, void* batch) {
  Parser* pr = static_cast<Parser*>(h);
  Parser* b = static_cast<Parser*>(batch);
  if (pr != nullptr) {
    std::vector<Columns> cols(b->fields.size());
    for (size_t i = 0; i < cols.size(); i++) {
      cols[i].swap(b->fields[i]);
      cols[i].clear();
    }
    std::lock_guard<std::mutex> lock(pr->spare_mu);
    // one being read, one queued, one being filled
    if (pr->spares.size() < 3) pr->spares.emplace_back(std::move(cols));
  }
  delete b;
}

}  // extern "C"
