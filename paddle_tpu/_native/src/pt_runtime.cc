// paddle_tpu native runtime (libpaddle_tpu_rt.so)
//
// TPU-native C++ runtime services around the JAX/XLA compute path, mirroring
// the reference framework's native subsystems:
//   - flags registry        (reference: paddle/fluid/platform/flags.cc +
//                            pybind/global_value_getter_setter.cc)
//   - stat monitor          (reference: paddle/fluid/platform/monitor.{h,cc},
//                            StatRegistry monitor.h:77, STAT_ADD :130)
//   - host profiler         (reference: paddle/fluid/platform/profiler.{h,cc},
//                            RecordEvent profiler.h:127; chrome-trace export
//                            replaces the CUPTI/profiler.proto timeline)
//   - nan/inf scanner       (reference: framework/details/nan_inf_utils*.cc,
//                            CheckVarHasNanOrInf nan_inf_utils.h:29)
//   - shared-memory ring    (reference: memory/allocation/mmap_allocator.* +
//                            operators/reader/lod_tensor_blocking_queue.h —
//                            the multiprocess DataLoader transport)
//
// Design: one translation unit, a flat C ABI consumed from Python via ctypes
// (the reference used pybind11; this build binds through the C ABI to keep the
// runtime reusable from any host language). All services are thread-safe.
//
// Build: g++ -O3 -shared -fPIC -std=c++17 -pthread pt_runtime.cc -lrt

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

#define PT_API extern "C" __attribute__((visibility("default")))

// ---------------------------------------------------------------------------
// Flags registry
// ---------------------------------------------------------------------------

namespace {
std::mutex g_flags_mu;
std::map<std::string, std::string>& flags_map() {
  static std::map<std::string, std::string> m;
  return m;
}
}  // namespace

PT_API void pt_flag_set(const char* name, const char* value) {
  std::lock_guard<std::mutex> lk(g_flags_mu);
  flags_map()[name] = value;
}

// Returns length written (excl. NUL), or -1 if the flag is unset.
PT_API int pt_flag_get(const char* name, char* buf, int buflen) {
  std::lock_guard<std::mutex> lk(g_flags_mu);
  auto it = flags_map().find(name);
  if (it == flags_map().end()) return -1;
  int n = (int)it->second.size();
  if (buf && buflen > 0) {
    int c = n < buflen - 1 ? n : buflen - 1;
    memcpy(buf, it->second.data(), c);
    buf[c] = '\0';
  }
  return n;
}

PT_API int pt_flag_list(char* buf, int buflen) {
  std::lock_guard<std::mutex> lk(g_flags_mu);
  std::string out;
  for (auto& kv : flags_map()) {
    out += kv.first;
    out += '\n';
  }
  int n = (int)out.size();
  if (buf && buflen > 0) {
    int c = n < buflen - 1 ? n : buflen - 1;
    memcpy(buf, out.data(), c);
    buf[c] = '\0';
  }
  return n;
}

// ---------------------------------------------------------------------------
// Stat monitor (StatRegistry analog)
// ---------------------------------------------------------------------------

namespace {
std::mutex g_stats_mu;
std::map<std::string, std::atomic<long long>*>& stats_map() {
  static std::map<std::string, std::atomic<long long>*> m;
  return m;
}

std::atomic<long long>* stat_cell(const char* name) {
  std::lock_guard<std::mutex> lk(g_stats_mu);
  auto& m = stats_map();
  auto it = m.find(name);
  if (it == m.end()) {
    it = m.emplace(name, new std::atomic<long long>(0)).first;
  }
  return it->second;
}
}  // namespace

PT_API void pt_stat_add(const char* name, long long v) {
  stat_cell(name)->fetch_add(v, std::memory_order_relaxed);
}

// keep the largest value seen (a worst case beside the sums)
PT_API void pt_stat_max(const char* name, long long v) {
  std::atomic<long long>* cell = stat_cell(name);
  long long cur = cell->load(std::memory_order_relaxed);
  while (cur < v && !cell->compare_exchange_weak(
                        cur, v, std::memory_order_relaxed)) {
  }
}

PT_API long long pt_stat_get(const char* name) {
  return stat_cell(name)->load(std::memory_order_relaxed);
}

PT_API void pt_stat_reset(const char* name) {
  stat_cell(name)->store(0, std::memory_order_relaxed);
}

PT_API int pt_stat_list(char* buf, int buflen) {
  std::lock_guard<std::mutex> lk(g_stats_mu);
  std::string out;
  for (auto& kv : stats_map()) {
    out += kv.first;
    out += '\n';
  }
  int n = (int)out.size();
  if (buf && buflen > 0) {
    int c = n < buflen - 1 ? n : buflen - 1;
    memcpy(buf, out.data(), c);
    buf[c] = '\0';
  }
  return n;
}

// ---------------------------------------------------------------------------
// Profiler: thread-safe event log, chrome-trace JSON export
// ---------------------------------------------------------------------------

namespace {
struct ProfEvent {
  std::string name;
  std::string cat;
  long long start_ns;
  long long end_ns;
  long long tid;
};

std::mutex g_prof_mu;
std::vector<ProfEvent> g_prof_events;
std::atomic<int> g_prof_enabled{0};

long long now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (long long)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

// Minimal JSON string escaping for event names.
void json_escape(const std::string& in, std::string* out) {
  for (char c : in) {
    switch (c) {
      case '"': *out += "\\\""; break;
      case '\\': *out += "\\\\"; break;
      case '\n': *out += "\\n"; break;
      case '\t': *out += "\\t"; break;
      default:
        if ((unsigned char)c < 0x20) {
          char tmp[8];
          snprintf(tmp, sizeof(tmp), "\\u%04x", c);
          *out += tmp;
        } else {
          *out += c;
        }
    }
  }
}
}  // namespace

PT_API long long pt_prof_now_ns() { return now_ns(); }

PT_API void pt_prof_enable() { g_prof_enabled.store(1); }
PT_API void pt_prof_disable() { g_prof_enabled.store(0); }
PT_API int pt_prof_enabled() { return g_prof_enabled.load(); }

PT_API void pt_prof_event(const char* name, const char* cat,
                          long long start_ns, long long end_ns,
                          long long tid) {
  if (!g_prof_enabled.load(std::memory_order_relaxed)) return;
  std::lock_guard<std::mutex> lk(g_prof_mu);
  g_prof_events.push_back(
      ProfEvent{name, cat ? cat : "op", start_ns, end_ns, tid});
}

PT_API void pt_prof_clear() {
  std::lock_guard<std::mutex> lk(g_prof_mu);
  g_prof_events.clear();
}

PT_API long long pt_prof_count() {
  std::lock_guard<std::mutex> lk(g_prof_mu);
  return (long long)g_prof_events.size();
}

// Writes a chrome://tracing "traceEvents" JSON file. Returns event count,
// or -1 on IO error.
PT_API long long pt_prof_export(const char* path) {
  std::lock_guard<std::mutex> lk(g_prof_mu);
  FILE* f = fopen(path, "w");
  if (!f) return -1;
  fputs("{\"traceEvents\":[\n", f);
  for (size_t i = 0; i < g_prof_events.size(); ++i) {
    const ProfEvent& e = g_prof_events[i];
    std::string name, cat;
    json_escape(e.name, &name);
    json_escape(e.cat, &cat);
    // chrome trace uses microsecond floats
    fprintf(f,
            "{\"name\":\"%s\",\"cat\":\"%s\",\"ph\":\"X\",\"ts\":%.3f,"
            "\"dur\":%.3f,\"pid\":%d,\"tid\":%lld}%s\n",
            name.c_str(), cat.c_str(), e.start_ns / 1000.0,
            (e.end_ns - e.start_ns) / 1000.0, (int)getpid(), e.tid,
            i + 1 < g_prof_events.size() ? "," : "");
  }
  fputs("]}\n", f);
  fclose(f);
  return (long long)g_prof_events.size();
}

// Aggregated per-name summary: "name\tcalls\ttotal_ns\tmax_ns\n" rows sorted
// by total time desc (the reference's profiler.cc PrintProfiler table analog).
PT_API int pt_prof_summary(char* buf, int buflen) {
  std::lock_guard<std::mutex> lk(g_prof_mu);
  struct Agg {
    long long calls = 0, total = 0, maxv = 0;
  };
  std::map<std::string, Agg> agg;
  for (const auto& e : g_prof_events) {
    Agg& a = agg[e.name];
    long long d = e.end_ns - e.start_ns;
    a.calls++;
    a.total += d;
    if (d > a.maxv) a.maxv = d;
  }
  std::vector<std::pair<std::string, Agg>> rows(agg.begin(), agg.end());
  std::sort(rows.begin(), rows.end(), [](const auto& x, const auto& y) {
    return x.second.total > y.second.total;
  });
  std::string out;
  for (auto& r : rows) {
    out += r.first + "\t" + std::to_string(r.second.calls) + "\t" +
           std::to_string(r.second.total) + "\t" +
           std::to_string(r.second.maxv) + "\n";
  }
  int n = (int)out.size();
  if (buf && buflen > 0) {
    int c = n < buflen - 1 ? n : buflen - 1;
    memcpy(buf, out.data(), c);
    buf[c] = '\0';
  }
  return n;
}

// ---------------------------------------------------------------------------
// NaN/Inf scanners (host-side fast path for FLAGS_check_nan_inf)
// ---------------------------------------------------------------------------

PT_API long long pt_count_nonfinite_f32(const float* data, long long n) {
  long long bad = 0;
  for (long long i = 0; i < n; ++i) {
    if (!std::isfinite(data[i])) ++bad;
  }
  return bad;
}

PT_API long long pt_count_nonfinite_f64(const double* data, long long n) {
  long long bad = 0;
  for (long long i = 0; i < n; ++i) {
    if (!std::isfinite(data[i])) ++bad;
  }
  return bad;
}

// bfloat16 is the high half of a float32: non-finite iff exponent bits
// (bits 14..7 of the u16) are all ones.
PT_API long long pt_count_nonfinite_bf16(const uint16_t* data, long long n) {
  long long bad = 0;
  for (long long i = 0; i < n; ++i) {
    if ((data[i] & 0x7F80u) == 0x7F80u) ++bad;
  }
  return bad;
}

// float16: exponent bits 14..10 all ones.
PT_API long long pt_count_nonfinite_f16(const uint16_t* data, long long n) {
  long long bad = 0;
  for (long long i = 0; i < n; ++i) {
    if ((data[i] & 0x7C00u) == 0x7C00u) ++bad;
  }
  return bad;
}

// ---------------------------------------------------------------------------
// Shared-memory ring buffer (multiprocess DataLoader transport)
//
// SPSC/MPSC circular byte buffer in POSIX shared memory with process-shared
// pthread mutex + condvars. Messages are 8-byte-length-prefixed and copied in
// up to two parts on wrap-around. One writer side per worker process; the
// parent reads. Capacity must exceed the largest single message.
// ---------------------------------------------------------------------------

namespace {
struct RingHeader {
  uint64_t magic;          // validity check
  int64_t capacity;        // data bytes
  int64_t head;            // read offset
  int64_t tail;            // write offset
  int64_t used;            // bytes in buffer
  int32_t closed;          // producer closed
  int32_t _pad;
  pthread_mutex_t mu;
  pthread_cond_t nonempty;
  pthread_cond_t nonfull;
};

constexpr uint64_t kRingMagic = 0x70745f72696e6701ULL;

struct Ring {
  RingHeader* hdr;
  char* data;
  size_t map_len;
  std::string name;
  bool owner;
};

char* ring_data(RingHeader* h) {
  return reinterpret_cast<char*>(h) + sizeof(RingHeader);
}

void abs_deadline(struct timespec* ts, int timeout_ms) {
  clock_gettime(CLOCK_MONOTONIC, ts);
  ts->tv_sec += timeout_ms / 1000;
  ts->tv_nsec += (long)(timeout_ms % 1000) * 1000000L;
  if (ts->tv_nsec >= 1000000000L) {
    ts->tv_sec += 1;
    ts->tv_nsec -= 1000000000L;
  }
}
}  // namespace

PT_API void* pt_ring_create(const char* name, long long capacity) {
  shm_unlink(name);  // stale segment from a crashed prior run
  int fd = shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0) return nullptr;
  size_t total = sizeof(RingHeader) + (size_t)capacity;
  if (ftruncate(fd, total) != 0) {
    close(fd);
    shm_unlink(name);
    return nullptr;
  }
  void* mem = mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) {
    shm_unlink(name);
    return nullptr;
  }
  RingHeader* h = (RingHeader*)mem;
  memset(h, 0, sizeof(RingHeader));
  h->capacity = capacity;

  pthread_mutexattr_t ma;
  pthread_mutexattr_init(&ma);
  pthread_mutexattr_setpshared(&ma, PTHREAD_PROCESS_SHARED);
  // robust so a worker dying with the lock held doesn't hang the parent
  pthread_mutexattr_setrobust(&ma, PTHREAD_MUTEX_ROBUST);
  pthread_mutex_init(&h->mu, &ma);

  pthread_condattr_t ca;
  pthread_condattr_init(&ca);
  pthread_condattr_setpshared(&ca, PTHREAD_PROCESS_SHARED);
  pthread_condattr_setclock(&ca, CLOCK_MONOTONIC);
  pthread_cond_init(&h->nonempty, &ca);
  pthread_cond_init(&h->nonfull, &ca);

  h->magic = kRingMagic;
  Ring* r = new Ring{h, ring_data(h), total, name, true};
  return r;
}

PT_API void* pt_ring_open(const char* name) {
  int fd = shm_open(name, O_RDWR, 0600);
  if (fd < 0) return nullptr;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    close(fd);
    return nullptr;
  }
  void* mem =
      mmap(nullptr, st.st_size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) return nullptr;
  RingHeader* h = (RingHeader*)mem;
  if (h->magic != kRingMagic) {
    munmap(mem, st.st_size);
    return nullptr;
  }
  Ring* r = new Ring{h, ring_data(h), (size_t)st.st_size, name, false};
  return r;
}

namespace {
int lock_mu(RingHeader* h) {
  int rc = pthread_mutex_lock(&h->mu);
  if (rc == EOWNERDEAD) {
    // A process died holding the lock (worker killed mid-write). Committed
    // messages (head..head+used) are intact, but tail may have advanced past
    // an uncommitted partial write — resync it and close the stream so the
    // consumer drains what is valid and the supervisor restarts the worker.
    h->tail = (h->head + h->used) % h->capacity;
    h->closed = 1;
    pthread_mutex_consistent(&h->mu);
    rc = 0;
  }
  return rc;
}
}  // namespace

// Blocking write with timeout. Returns 0 ok, -1 timeout, -2 closed/error,
// -3 message larger than capacity.
PT_API int pt_ring_write(void* ring, const void* src, long long len,
                         int timeout_ms) {
  Ring* r = (Ring*)ring;
  RingHeader* h = r->hdr;
  long long need = len + 8;
  if (need > h->capacity) return -3;
  if (lock_mu(h) != 0) return -2;
  struct timespec dl;
  abs_deadline(&dl, timeout_ms);
  while (h->capacity - h->used < need) {
    if (h->closed) {
      pthread_mutex_unlock(&h->mu);
      return -2;
    }
    int rc = pthread_cond_timedwait(&h->nonfull, &h->mu, &dl);
    if (rc == ETIMEDOUT) {
      pthread_mutex_unlock(&h->mu);
      return -1;
    }
  }
  // write 8-byte length, then payload, both possibly in two parts
  char lenbuf[8];
  memcpy(lenbuf, &len, 8);
  const char* parts[2] = {lenbuf, (const char*)src};
  long long plens[2] = {8, len};
  for (int p = 0; p < 2; ++p) {
    long long off = 0;
    while (off < plens[p]) {
      long long pos = h->tail % h->capacity;
      long long chunk = plens[p] - off;
      if (chunk > h->capacity - pos) chunk = h->capacity - pos;
      memcpy(r->data + pos, parts[p] + off, chunk);
      h->tail = (h->tail + chunk) % h->capacity;
      off += chunk;
    }
  }
  h->used += need;
  pthread_cond_signal(&h->nonempty);
  pthread_mutex_unlock(&h->mu);
  return 0;
}

// Blocks until a message is available; returns its length, -1 on timeout,
// -2 if closed and drained.
PT_API long long pt_ring_next_len(void* ring, int timeout_ms) {
  Ring* r = (Ring*)ring;
  RingHeader* h = r->hdr;
  if (lock_mu(h) != 0) return -2;
  struct timespec dl;
  abs_deadline(&dl, timeout_ms);
  while (h->used < 8) {
    if (h->closed) {
      pthread_mutex_unlock(&h->mu);
      return -2;
    }
    int rc = pthread_cond_timedwait(&h->nonempty, &h->mu, &dl);
    if (rc == ETIMEDOUT) {
      pthread_mutex_unlock(&h->mu);
      return -1;
    }
  }
  long long len = 0;
  long long pos = h->head % h->capacity;
  char lenbuf[8];
  for (int i = 0; i < 8; ++i) lenbuf[i] = r->data[(pos + i) % h->capacity];
  memcpy(&len, lenbuf, 8);
  pthread_mutex_unlock(&h->mu);
  return len;
}

// Pops the next message into buf (must be >= its length). Returns bytes
// copied, or -2 on closed/error. Call after pt_ring_next_len.
PT_API long long pt_ring_read(void* ring, void* buf, long long buflen) {
  Ring* r = (Ring*)ring;
  RingHeader* h = r->hdr;
  if (lock_mu(h) != 0) return -2;
  if (h->used < 8) {
    pthread_mutex_unlock(&h->mu);
    return -2;
  }
  long long len = 0;
  char lenbuf[8];
  long long pos = h->head % h->capacity;
  for (int i = 0; i < 8; ++i) lenbuf[i] = r->data[(pos + i) % h->capacity];
  memcpy(&len, lenbuf, 8);
  if (len > buflen) {
    pthread_mutex_unlock(&h->mu);
    return -2;
  }
  h->head = (h->head + 8) % h->capacity;
  long long off = 0;
  while (off < len) {
    long long p = h->head % h->capacity;
    long long chunk = len - off;
    if (chunk > h->capacity - p) chunk = h->capacity - p;
    memcpy((char*)buf + off, r->data + p, chunk);
    h->head = (h->head + chunk) % h->capacity;
    off += chunk;
  }
  h->used -= len + 8;
  pthread_cond_broadcast(&h->nonfull);
  pthread_mutex_unlock(&h->mu);
  return len;
}

PT_API void pt_ring_close_producer(void* ring) {
  Ring* r = (Ring*)ring;
  RingHeader* h = r->hdr;
  if (lock_mu(h) != 0) return;
  h->closed = 1;
  pthread_cond_broadcast(&h->nonempty);
  pthread_cond_broadcast(&h->nonfull);
  pthread_mutex_unlock(&h->mu);
}

PT_API void pt_ring_free(void* ring, int unlink_shm) {
  Ring* r = (Ring*)ring;
  if (unlink_shm) shm_unlink(r->name.c_str());
  munmap(r->hdr, r->map_len);
  delete r;
}

PT_API long long pt_ring_used(void* ring) {
  Ring* r = (Ring*)ring;
  RingHeader* h = r->hdr;
  if (lock_mu(h) != 0) return -1;
  long long u = h->used;
  pthread_mutex_unlock(&h->mu);
  return u;
}

// ---------------------------------------------------------------------------
// Version / smoke
// ---------------------------------------------------------------------------

PT_API int pt_runtime_version() { return 1; }
