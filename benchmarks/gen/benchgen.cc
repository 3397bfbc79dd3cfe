// benchgen: the benchmark's own copy of native/dngen.cc (the yardstick
// may not move with the program).  Byte-for-byte the same records for
// the same arguments; the one addition is that every generated field
// is also written into column arrays, which the plain reference
// (benchmarks/reference/) groups without ever parsing the JSON.
//
// dngen: fast muskie-log-like JSON test-data generator.
//
// Same record shape and distributions as tools/mktestdata (itself the
// behavioral equivalent of the reference's tools/mktestdata:1-192):
// linearly increasing timestamps, small-cardinality discrete fields,
// operation dependent on req.method, nullable/omitted req.caller, fixed
// status codes, mixed-distribution latencies, large-range dataSize.
// Exists so benchmarks can generate data at ingest-comparable rates
// (the Python generator tops out around 100k records/s, which would
// dominate large-scale benchmark wall time).
//
// Exposed as a plain C ABI for ctypes.

#include <cstdint>
#include <cstdio>
#include <cstring>

namespace {

struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed ? seed : 0x9e3779b97f4a7c15ull) {}
  uint64_t next() {
    // xorshift64*
    s ^= s >> 12;
    s ^= s << 25;
    s ^= s >> 27;
    return s * 0x2545F4914F6CDD1Dull;
  }
  // uniform in [0, n)
  uint64_t below(uint64_t n) { return next() % n; }
  double unit() { return (next() >> 11) * (1.0 / 9007199254740992.0); }
};

const char* const kHosts[] = {"ralph", "janey", "kearney", "sherri",
                              "wendell"};
const char* const kMethods[] = {"HEAD", "GET", "PUT", "DELETE"};
const char* const kOpsHead[] = {"headstorage", "headpublicstorage"};
const char* const kOpsGet[] = {"getjoberrors", "getpublicstorage",
                               "getstorage"};
const char* const kOpsPut[] = {"putdirectory", "putpublicobject",
                               "putobject"};
const char* const kOpsDelete[] = {"deletestorage",
                                  "deletepublicstorage"};
const int kStatus[] = {200, 204, 400, 404, 499, 500, 503};

int probdist(Rng& rng) {
  // (0.4, 1, 5), (0.3, 20, 30), (0.1, 100, 200), (rest, 1024, 4096)
  double r = rng.unit();
  double lo, hi;
  if (r < 0.4) {
    lo = 1; hi = 5;
  } else if (r < 0.7) {
    lo = 20; hi = 30;
  } else if (r < 0.8) {
    lo = 100; hi = 200;
  } else {
    lo = 1024; hi = 4096;
  }
  double v = rng.unit() * (hi - lo) + lo;
  return static_cast<int>(v + 0.5);
}

// days_from_civil inverse: epoch day -> y/m/d (Howard Hinnant)
void civil_from_days(int64_t z, int* y, unsigned* m, unsigned* d) {
  z += 719468;
  const int64_t era = (z >= 0 ? z : z - 146096) / 146097;
  const unsigned doe = static_cast<unsigned>(z - era * 146097);
  const unsigned yoe =
      (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
  const int64_t yy = static_cast<int64_t>(yoe) + era * 400;
  const unsigned doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
  const unsigned mp = (5 * doy + 2) / 153;
  *d = doy - (153 * mp + 2) / 5 + 1;
  *m = mp + (mp < 10 ? 3 : -9);
  *y = static_cast<int>(yy + (*m <= 2));
}

}  // namespace

extern "C" {

// Generates records [start, start+n) of nrecords into buf; returns
// bytes written, or -1 if the buffer is too small (the guard demands
// 512 bytes of headroom before each record, so size 512 bytes per
// record).
//
// The column pointers take record i's fields at index i - start:
// host, method, op (index into the ten operations in kOps* order),
// caller (0 admin, 1 poseidon, 2 null, 3 omitted), url number, status
// code, latency, dataLatency, dataSize and the timestamp in epoch ms.
int64_t bench_gen(char* buf, int64_t bufcap, int64_t start, int64_t n,
                  int64_t nrecords, int64_t mindate_ms,
                  int64_t maxdate_ms, uint64_t seed, uint8_t* c_host,
                  uint8_t* c_method, uint8_t* c_op, uint8_t* c_caller,
                  int16_t* c_url, int16_t* c_status, int32_t* c_latency,
                  int32_t* c_dlatency, int64_t* c_dsize, int64_t* c_ts) {
  char* p = buf;
  char* end = buf + bufcap;
  for (int64_t i = start; i < start + n; i++) {
    if (end - p < 512)
      return -1;
    Rng rng(seed * 0x9E3779B97F4A7C15ull + i * 0xBF58476D1CE4E5B9ull);
    rng.next();

    int64_t ts = mindate_ms +
        static_cast<int64_t>((static_cast<double>(i) / nrecords) *
                             (maxdate_ms - mindate_ms) + 0.5);
    int64_t secs = ts / 1000;
    int ms = static_cast<int>(ts % 1000);
    int64_t days = secs / 86400;
    int rem = static_cast<int>(secs % 86400);
    int y;
    unsigned mo, dd;
    civil_from_days(days, &y, &mo, &dd);

    unsigned hi = static_cast<unsigned>(rng.below(5));
    const char* host = kHosts[hi];
    unsigned mi = static_cast<unsigned>(rng.below(4));
    const char* method = kMethods[mi];
    const char* op;
    unsigned oi;
    switch (mi) {
      case 0: oi = rng.below(2); op = kOpsHead[oi]; break;
      case 1: oi = rng.below(3); op = kOpsGet[oi]; oi += 2; break;
      case 2: oi = rng.below(3); op = kOpsPut[oi]; oi += 5; break;
      default: oi = rng.below(2); op = kOpsDelete[oi]; oi += 8; break;
    }
    unsigned caller = static_cast<unsigned>(rng.below(4));
    int url = static_cast<int>(rng.below(500));
    int status = kStatus[rng.below(7)];
    int latency = probdist(rng);
    int dlatency = probdist(rng);
    int64_t dsize =
        static_cast<int64_t>(rng.unit() * 1073741824.0 + 0.5);

    const int64_t k = i - start;
    c_host[k] = static_cast<uint8_t>(hi);
    c_method[k] = static_cast<uint8_t>(mi);
    c_op[k] = static_cast<uint8_t>(oi);
    c_caller[k] = static_cast<uint8_t>(caller);
    c_url[k] = static_cast<int16_t>(url);
    c_status[k] = static_cast<int16_t>(status);
    c_latency[k] = latency;
    c_dlatency[k] = dlatency;
    c_dsize[k] = dsize;
    c_ts[k] = ts;

    p += snprintf(
        p, static_cast<size_t>(end - p),
        "{\"time\":\"%04d-%02u-%02uT%02d:%02d:%02d.%03dZ\","
        "\"host\":\"%s\",\"req\":{\"method\":\"%s\","
        "\"url\":\"/random/url/number/%d\"",
        y, mo, dd, rem / 3600, (rem / 60) % 60, rem % 60, ms, host,
        method, url);
    if (caller == 0)
      p += snprintf(p, static_cast<size_t>(end - p),
                    ",\"caller\":\"admin\"");
    else if (caller == 1)
      p += snprintf(p, static_cast<size_t>(end - p),
                    ",\"caller\":\"poseidon\"");
    else if (caller == 2)
      p += snprintf(p, static_cast<size_t>(end - p),
                    ",\"caller\":null");
    // caller == 3: omitted
    p += snprintf(
        p, static_cast<size_t>(end - p),
        "},\"operation\":\"%s\",\"res\":{\"statusCode\":%d},"
        "\"latency\":%d,\"dataLatency\":%d,\"dataSize\":%lld}\n",
        op, status, latency, dlatency,
        static_cast<long long>(dsize));
  }
  return p - buf;
}

}  // extern "C"
