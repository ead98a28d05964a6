// Native chunk-datapath sidecar: the C++-grade hot path for the
// datanode's bulk verbs (WriteChunksCommit / ReadChunks / WriteChunk /
// ReadChunk analogs).
//
// Role analog of the reference datanode's Netty native-epoll gRPC
// transport + mapped-channel chunk IO (container-service
// transport/server/GrpcXceiverService.java:42, keyvalue/helpers/
// ChunkUtils.java:109-156): the reference moves chunk bytes through
// native code end-to-end; a Python gRPC stack pays ~65% of every
// WriteChunk round trip in interpreter-driven transport. This sidecar
// owns frame parse -> pwrite/pread -> CRC32C verify -> fsync on its
// own TCP listener inside the datanode
// process; Python keeps the control plane (token verification, write
// fences, layout gates, block commits) via three callbacks that are
// invoked once per STREAM, not per chunk.
//
// Wire protocol (all little-endian; both ends are in this repo):
//   frame := u32 body_len | u8 tag | body
//   client->server tags:
//     0x01 WHDR   body = opaque JSON header (passed to the auth
//                 callback verbatim; C++ never parses JSON)
//     0x05 RHDR   body = opaque JSON header (read stream)
//     0x02 CHUNK  body = u64 offset | u32 length | payload
//     0x06 RCHUNK body = u64 offset | u32 length | u8 vtype |
//                 u32 bytes_per_crc | u32 n_crcs | u32 crcs[n]
//                 (vtype: 0 = no verify, 1 = CRC32C)
//     0x03 END    body = u8 sync  (write: fsync before the commit)
//   server->client tags:
//     0x81 STATUS body = JSON: {} on success, {"error":{code,message}}
//     0x82 DATA   body = one requested chunk's bytes (read streams,
//                 request order)
//
// Python callbacks (ctypes; the wrapper acquires the GIL):
//   auth(hdr, len, is_write, out, cap) -> n:
//     out = u8 ok | body; ok=1 -> body is the absolute block-file
//     path (container resolved, token verified, fence bound);
//     ok=0 -> body is an error JSON forwarded to the client.
//   done(hdr, len, is_write, bytes, chunks, out, cap) -> n:
//     stream finished; Python applies the piggybacked block commit
//     (put_block) and metrics. Same out convention (ok=1 body empty).
//   fail(hdr, len): a read-side CRC32C verification failed; Python
//     marks the container unhealthy (OnDemandContainerDataScanner
//     trigger analog).

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <limits.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/mman.h>
#include <sys/sendfile.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <sys/un.h>
#include <unistd.h>

#include <array>
#include <atomic>
#include <mutex>
#include <set>
#include <string>
#include <thread>
#include <vector>

#if defined(__SSE4_2__)
#include <nmmintrin.h>
#endif

namespace {

// ----------------------------------------------------------------- crc32c
// Castagnoli CRC with init/xorout 0xFFFFFFFF, matching
// utils/checksum.crc32c (values compared against the stored big-endian
// u32s the client decodes for us).
uint32_t crc32c_sw_table[256];
std::once_flag crc_once;

void crc32c_init() {
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++) c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
    crc32c_sw_table[i] = c;
  }
}

#if defined(__SSE4_2__)
// The crc32 instruction has a 3-cycle latency, so a single dependency
// chain tops out near 4 GiB/s — a third of what the verify path needs.
// Run three independent chains over adjacent blocks and splice them
// with GF(2) "advance the CRC past N zero bytes" operators, the same
// interleave zlib/ISA-L use.  The operators for the two fixed block
// sizes are precomputed into 4x256 lookup tables at first use.
constexpr size_t kCrcLongBlk = 4096;
constexpr size_t kCrcShortBlk = 256;
uint32_t crc_shift_long[4][256];
uint32_t crc_shift_short[4][256];
std::once_flag crc_shift_once;

uint32_t gf2_times(const uint32_t* mat, uint32_t vec) {
  uint32_t sum = 0;
  while (vec) {
    if (vec & 1) sum ^= *mat;
    vec >>= 1;
    mat++;
  }
  return sum;
}

void gf2_square(uint32_t* sq, const uint32_t* mat) {
  for (int n = 0; n < 32; n++) sq[n] = gf2_times(mat, mat[n]);
}

// Build the 32x32 GF(2) matrix that advances a CRC-32C register past
// `len` zero bytes, by repeated squaring of the one-bit shift operator.
void crc_zeros_op(uint32_t* even, size_t len) {
  uint32_t odd[32];
  odd[0] = 0x82F63B78u;  // reflected Castagnoli polynomial
  uint32_t row = 1;
  for (int n = 1; n < 32; n++) {
    odd[n] = row;
    row <<= 1;
  }
  gf2_square(even, odd);  // two squarings: odd is now "shift 1 bit",
  gf2_square(odd, even);  // even/odd alternate 2-bit, 4-bit, ...
  do {
    gf2_square(even, odd);
    len >>= 1;
    if (len == 0) return;
    gf2_square(odd, even);
    len >>= 1;
  } while (len);
  for (int n = 0; n < 32; n++) even[n] = odd[n];
}

void crc_zeros_table(uint32_t zeros[][256], size_t len) {
  uint32_t op[32];
  crc_zeros_op(op, len);
  for (uint32_t n = 0; n < 256; n++) {
    zeros[0][n] = gf2_times(op, n);
    zeros[1][n] = gf2_times(op, n << 8);
    zeros[2][n] = gf2_times(op, n << 16);
    zeros[3][n] = gf2_times(op, n << 24);
  }
}

void crc_shift_init() {
  crc_zeros_table(crc_shift_long, kCrcLongBlk);
  crc_zeros_table(crc_shift_short, kCrcShortBlk);
}

inline uint32_t crc_shift(const uint32_t zeros[][256], uint32_t crc) {
  return zeros[0][crc & 0xFF] ^ zeros[1][(crc >> 8) & 0xFF] ^
         zeros[2][(crc >> 16) & 0xFF] ^ zeros[3][crc >> 24];
}

uint64_t load_u64(const uint8_t* p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;
}
#endif  // __SSE4_2__

uint32_t crc32c(const uint8_t* p, size_t n) {
  uint32_t s = 0xFFFFFFFFu;
#if defined(__SSE4_2__)
  std::call_once(crc_shift_once, crc_shift_init);
  while (n >= 3 * kCrcLongBlk) {
    uint32_t c1 = 0, c2 = 0;
    const uint8_t* end = p + kCrcLongBlk;
    do {
      s = (uint32_t)_mm_crc32_u64(s, load_u64(p));
      c1 = (uint32_t)_mm_crc32_u64(c1, load_u64(p + kCrcLongBlk));
      c2 = (uint32_t)_mm_crc32_u64(c2, load_u64(p + 2 * kCrcLongBlk));
      p += 8;
    } while (p < end);
    s = crc_shift(crc_shift_long, s) ^ c1;
    s = crc_shift(crc_shift_long, s) ^ c2;
    p += 2 * kCrcLongBlk;
    n -= 3 * kCrcLongBlk;
  }
  while (n >= 3 * kCrcShortBlk) {
    uint32_t c1 = 0, c2 = 0;
    const uint8_t* end = p + kCrcShortBlk;
    do {
      s = (uint32_t)_mm_crc32_u64(s, load_u64(p));
      c1 = (uint32_t)_mm_crc32_u64(c1, load_u64(p + kCrcShortBlk));
      c2 = (uint32_t)_mm_crc32_u64(c2, load_u64(p + 2 * kCrcShortBlk));
      p += 8;
    } while (p < end);
    s = crc_shift(crc_shift_short, s) ^ c1;
    s = crc_shift(crc_shift_short, s) ^ c2;
    p += 2 * kCrcShortBlk;
    n -= 3 * kCrcShortBlk;
  }
  while (n >= 8) {
    s = (uint32_t)_mm_crc32_u64(s, load_u64(p));
    p += 8;
    n -= 8;
  }
  while (n) {
    s = _mm_crc32_u8(s, *p++);
    n--;
  }
#else
  std::call_once(crc_once, crc32c_init);
  while (n) {
    s = (s >> 8) ^ crc32c_sw_table[(s ^ *p++) & 0xFF];
    n--;
  }
#endif
  return s ^ 0xFFFFFFFFu;
}

// ------------------------------------------------------------- callbacks
typedef int32_t (*dp_auth_cb)(const uint8_t*, uint32_t, int32_t, uint8_t*,
                              uint32_t);
typedef int32_t (*dp_done_cb)(const uint8_t*, uint32_t, int32_t, uint64_t,
                              uint32_t, uint8_t*, uint32_t);
typedef void (*dp_fail_cb)(const uint8_t*, uint32_t);

constexpr uint8_t T_WHDR = 0x01, T_CHUNK = 0x02, T_END = 0x03, T_RHDR = 0x05,
                  T_RCHUNK = 0x06, T_STATUS = 0x81, T_DATA = 0x82;

constexpr uint32_t MAX_FRAME = 256u * 1024 * 1024;
constexpr uint32_t CB_OUT_CAP = 64u * 1024;

// grow-only byte buffer without value-initialization: vector::resize
// zero-fills on every grow, which costs a 1 MiB memset per chunk when
// frames alternate between tiny (END/status) and payload-sized
struct Buf {
  uint8_t* p = nullptr;
  size_t len = 0, cap = 0;
  ~Buf() { free(p); }
  // false on allocation failure: the old block stays valid (realloc's
  // nullptr return must not overwrite p — that leaked the block and
  // crashed the next memcpy); callers fail the frame/connection instead
  bool resize(size_t n) {
    if (n > cap) {
      size_t want = cap ? cap : 4096;
      while (want < n) want *= 2;
      uint8_t* np = (uint8_t*)realloc(p, want);
      if (!np) return false;
      p = np;
      cap = want;
    }
    len = n;
    return true;
  }
  uint8_t* data() { return p; }
  const uint8_t* data() const { return p; }
  size_t size() const { return len; }
  bool empty() const { return len == 0; }
  uint8_t operator[](size_t i) const { return p[i]; }
};

// ---------------------------------------------------------- buffer arena
// Page-aligned, size-classed, refcounted buffer pool. Payload bytes are
// received (readv) straight into leased buffers and sent (writev)
// straight out of them — the arena is the only payload-sized allocator
// on the native hot path, and it is exported to Python through the
// dp_buf_* capsule API so tests and the sidecar can observe (and, when
// useful, share) the same pool. Netty PooledByteBufAllocator analog.
struct PoolBuf {
  uint8_t* p = nullptr;
  size_t cap = 0;
  std::atomic<int> refs{1};
};

class Arena {
 public:
  static constexpr size_t kMinClass = 4096;        // one page
  static constexpr size_t kMaxClass = 64u << 20;   // retained classes
  static constexpr int kNClass = 15;               // 4 KiB .. 64 MiB

  PoolBuf* lease(size_t n) {
    size_t cap = kMinClass;
    while (cap < n) cap <<= 1;
    int cls = class_of(cap);
    PoolBuf* b = nullptr;
    if (cls >= 0) {
      std::lock_guard<std::mutex> g(mu_);
      auto& lst = free_[cls];
      if (!lst.empty()) {
        b = lst.back();
        lst.pop_back();
        free_bytes_.fetch_sub(cap);
      }
    }
    if (b) {
      b->refs.store(1);
    } else {
      void* mem = nullptr;
      if (posix_memalign(&mem, 4096, cap) != 0) return nullptr;
      b = new PoolBuf();
      b->p = (uint8_t*)mem;
      b->cap = cap;
    }
    uint64_t now = leased_bytes_.fetch_add(cap) + cap;
    uint64_t hw = high_water_.load();
    while (now > hw && !high_water_.compare_exchange_weak(hw, now)) {
    }
    return b;
  }

  void retain(PoolBuf* b) { b->refs.fetch_add(1); }

  void release(PoolBuf* b) {
    if (b->refs.fetch_sub(1) != 1) return;
    leased_bytes_.fetch_sub(b->cap);
    int cls = class_of(b->cap);
    if (cls >= 0 && free_bytes_.load() + b->cap <= max_retained()) {
      std::lock_guard<std::mutex> g(mu_);
      free_[cls].push_back(b);
      free_bytes_.fetch_add(b->cap);
      return;
    }
    free(b->p);
    delete b;
  }

  uint64_t stat(int which) const {
    switch (which) {
      case 0: return leased_bytes_.load();
      case 1: return free_bytes_.load();
      case 2: return high_water_.load();
      default: return 0;
    }
  }

 private:
  static int class_of(size_t cap) {
    if (cap < kMinClass || cap > kMaxClass || (cap & (cap - 1))) return -1;
    int i = 0;
    for (size_t c = kMinClass; c < cap; c <<= 1) i++;
    return i;
  }

  static uint64_t max_retained() {
    static uint64_t v = [] {
      const char* e = getenv("OZONE_TPU_POOL_MAX_MIB");
      long mib = e ? atol(e) : 256;
      if (mib < 16) mib = 16;
      return (uint64_t)mib << 20;
    }();
    return v;
  }

  std::mutex mu_;
  std::vector<PoolBuf*> free_[kNClass];
  std::atomic<uint64_t> leased_bytes_{0}, free_bytes_{0}, high_water_{0};
};

Arena g_arena;

struct Server {
  int listen_fd = -1;
  int port = 0;
  // local lane: an abstract-namespace unix socket speaking the same
  // frame protocol — ~1.5-2x the loopback-TCP throughput on one core
  // (no pseudo-NIC segmentation, one less queue). Co-located clients
  // learn the name over GetDatapathInfo and prefer it.
  int uds_fd = -1;
  std::string uds_name;
  dp_auth_cb auth = nullptr;
  dp_done_cb done = nullptr;
  dp_fail_cb fail = nullptr;
  std::atomic<bool> stop{false};
  std::atomic<int> active{0};
  std::mutex conn_mu;
  std::set<int> conns;
  std::thread acceptor;
  std::thread uds_acceptor;
};

bool read_full(int fd, void* buf, size_t n) {
  uint8_t* p = (uint8_t*)buf;
  while (n) {
    ssize_t r = recv(fd, p, n, 0);
    if (r <= 0) {
      if (r < 0 && errno == EINTR) continue;
      return false;
    }
    p += r;
    n -= (size_t)r;
  }
  return true;
}

bool write_full(int fd, const void* buf, size_t n) {
  const uint8_t* p = (const uint8_t*)buf;
  while (n) {
    ssize_t r = send(fd, p, n, MSG_NOSIGNAL);
    if (r < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    p += r;
    n -= (size_t)r;
  }
  return true;
}

// scatter receive: fill every iovec completely (headers into stack
// scratch, payload straight into a pooled buffer — one syscall for
// both on the common path)
bool readv_full(int fd, struct iovec* iov, int cnt) {
  while (cnt) {
    ssize_t r = readv(fd, iov, cnt);
    if (r <= 0) {
      if (r < 0 && errno == EINTR) continue;
      return false;
    }
    size_t adv = (size_t)r;
    while (cnt && adv) {
      size_t take = adv < iov->iov_len ? adv : iov->iov_len;
      iov->iov_base = (uint8_t*)iov->iov_base + take;
      iov->iov_len -= take;
      adv -= take;
      if (!iov->iov_len) {
        iov++;
        cnt--;
      }
    }
    while (cnt && !iov->iov_len) {
      iov++;
      cnt--;
    }
  }
  return true;
}

// gather send of a pre-built iovec array, IOV_MAX-batched
bool writev_full(int fd, struct iovec* iov, size_t cnt) {
#ifdef IOV_MAX
  const size_t kMaxIov = IOV_MAX;
#else
  const size_t kMaxIov = 1024;
#endif
  size_t done = 0;
  while (done < cnt) {
    while (done < cnt && !iov[done].iov_len) done++;
    if (done >= cnt) break;
    size_t batch = cnt - done < kMaxIov ? cnt - done : kMaxIov;
    ssize_t r = writev(fd, iov + done, (int)batch);
    if (r < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    size_t adv = (size_t)r;
    while (done < cnt && adv) {
      size_t take = adv < iov[done].iov_len ? adv : iov[done].iov_len;
      iov[done].iov_base = (uint8_t*)iov[done].iov_base + take;
      iov[done].iov_len -= take;
      adv -= take;
      if (!iov[done].iov_len) done++;
    }
  }
  return true;
}

bool send_frame(int fd, uint8_t tag, const void* body, uint32_t n) {
  uint8_t hdr[5];
  memcpy(hdr, &n, 4);
  hdr[4] = tag;
  struct iovec iov[2] = {{hdr, 5}, {(void*)body, n}};
  size_t total = 5 + n;
  while (total) {
    ssize_t r = writev(fd, iov, n ? 2 : 1);
    if (r < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    total -= (size_t)r;
    // advance iovecs
    size_t adv = (size_t)r;
    for (auto& v : iov) {
      size_t take = adv < v.iov_len ? adv : v.iov_len;
      v.iov_base = (uint8_t*)v.iov_base + take;
      v.iov_len -= take;
      adv -= take;
      if (!adv) break;
    }
  }
  return true;
}

bool read_frame(int fd, uint8_t* tag, Buf& body) {
  uint8_t hdr[5];
  if (!read_full(fd, hdr, 5)) return false;
  uint32_t n;
  memcpy(&n, hdr, 4);
  if (n > MAX_FRAME) return false;
  *tag = hdr[4];
  if (!body.resize(n)) return false;  // OOM: drop the connection
  if (n && !read_full(fd, body.data(), n)) return false;
  return true;
}

// minimal error JSON built in C (messages are plain ASCII we format)
std::string err_json(const char* code, const std::string& msg) {
  std::string out = "{\"error\":{\"code\":\"";
  out += code;
  out += "\",\"message\":\"";
  for (char c : msg) {
    if (c == '"' || c == '\\') out += '\\';
    if ((unsigned char)c >= 0x20) out += c;
  }
  out += "\"}}";
  return out;
}

bool send_status(int fd, const std::string& json) {
  return send_frame(fd, T_STATUS, json.data(), (uint32_t)json.size());
}

// drain client frames until END (keeps the connection consistent after
// an early error)
bool drain_to_end(int fd, Buf& scratch) {
  uint8_t tag;
  do {
    if (!read_frame(fd, &tag, scratch)) return false;
  } while (tag != T_END);
  return true;
}

// run a Python callback with the u8-ok|body out convention.
// ok_body gets the body; returns: 1 ok, 0 refused, -1 callback broke
int run_cb_auth(Server* s, const Buf& hdr, int is_write,
                std::string* ok_body) {
  uint8_t out[CB_OUT_CAP];  // stack: no per-call zeroing
  int32_t n = s->auth(hdr.data(), (uint32_t)hdr.size(), is_write, out,
                      CB_OUT_CAP);
  if (n < 1 || (uint32_t)n > CB_OUT_CAP) return -1;
  ok_body->assign((const char*)out + 1, (size_t)n - 1);
  return out[0] == 1 ? 1 : 0;
}

int run_cb_done(Server* s, const Buf& hdr, int is_write,
                uint64_t bytes, uint32_t chunks, std::string* body) {
  uint8_t out[CB_OUT_CAP];  // stack: no per-call zeroing
  int32_t n = s->done(hdr.data(), (uint32_t)hdr.size(), is_write, bytes,
                      chunks, out, CB_OUT_CAP);
  if (n < 1 || (uint32_t)n > CB_OUT_CAP) return -1;
  body->assign((const char*)out + 1, (size_t)n - 1);
  return out[0] == 1 ? 1 : 0;
}

// ------------------------------------------------------------ write path
bool handle_write(Server* s, int fd, const Buf& hdr,
                  Buf& scratch) {
  std::string body;
  int ok = run_cb_auth(s, hdr, 1, &body);
  if (ok <= 0) {
    if (!drain_to_end(fd, scratch)) return false;
    return send_status(fd, ok == 0 ? body
                                   : err_json("IO_EXCEPTION",
                                              "datapath auth callback failed"));
  }
  int file_fd = open(body.c_str(), O_RDWR | O_CREAT | O_CLOEXEC, 0644);
  std::string err;
  if (file_fd < 0)
    err = err_json("IO_EXCEPTION",
                   "open " + body + ": " + strerror(errno));
  uint64_t total = 0;
  uint32_t chunks = 0;
  bool sync = false;
  for (;;) {
    // parse the frame header ourselves: CHUNK payloads are scattered
    // (readv) straight into a pooled arena buffer, never staged
    // through the grow-only scratch
    uint8_t fh[5];
    if (!read_full(fd, fh, 5)) {
      if (file_fd >= 0) close(file_fd);
      return false;
    }
    uint32_t n;
    memcpy(&n, fh, 4);
    uint8_t tag = fh[4];
    if (n > MAX_FRAME) {
      if (file_fd >= 0) close(file_fd);
      return false;
    }
    if (tag == T_END) {
      if (!scratch.resize(n) || (n && !read_full(fd, scratch.data(), n))) {
        if (file_fd >= 0) close(file_fd);
        return false;
      }
      if (!scratch.empty()) sync = scratch[0] != 0;
      break;
    }
    if (tag != T_CHUNK || n < 12) {
      if (file_fd >= 0) close(file_fd);
      return false;  // protocol error: drop the connection
    }
    uint32_t len = n - 12;
    uint8_t chdr[12];
    PoolBuf* pb = nullptr;
    if (err.empty() && len) pb = g_arena.lease(len);
    if (pb || !len) {
      struct iovec iov[2] = {{chdr, 12}, {pb ? pb->p : nullptr, len}};
      if (!readv_full(fd, iov, len ? 2 : 1)) {
        if (pb) g_arena.release(pb);
        if (file_fd >= 0) close(file_fd);
        return false;
      }
    } else {
      // no buffer (failed stream or OOM): drain hdr + payload via
      // scratch to keep the connection framed
      if (!read_full(fd, chdr, 12) || !scratch.resize(len) ||
          (len && !read_full(fd, scratch.data(), len))) {
        if (file_fd >= 0) close(file_fd);
        return false;
      }
      if (err.empty())
        err = err_json("IO_EXCEPTION", "write buffer allocation failed");
      continue;
    }
    if (!err.empty()) {
      if (pb) g_arena.release(pb);
      continue;  // already failed: drain remaining
    }
    uint64_t off;
    uint32_t hdr_len;
    memcpy(&off, chdr, 8);
    memcpy(&hdr_len, chdr + 8, 4);
    if (hdr_len != len) {
      if (pb) g_arena.release(pb);
      if (file_fd >= 0) close(file_fd);
      return false;
    }
    const uint8_t* p = pb ? pb->p : nullptr;
    size_t left = len;
    uint64_t at = off;
    while (left) {
      ssize_t w = pwrite(file_fd, p, left, (off_t)at);
      if (w < 0) {
        if (errno == EINTR) continue;
        err = err_json("IO_EXCEPTION",
                       "pwrite: " + std::string(strerror(errno)));
        break;
      }
      p += w;
      at += (uint64_t)w;
      left -= (size_t)w;
    }
    if (pb) g_arena.release(pb);
    if (err.empty()) {
      total += len;
      chunks++;
    }
  }
  if (err.empty() && sync && file_fd >= 0 && fsync(file_fd) != 0)
    err = err_json("IO_EXCEPTION",
                   "fsync: " + std::string(strerror(errno)));
  if (file_fd >= 0) close(file_fd);
  if (!err.empty()) return send_status(fd, err);
  std::string done_body;
  int d = run_cb_done(s, hdr, 1, total, chunks, &done_body);
  if (d < 0)
    return send_status(
        fd, err_json("IO_EXCEPTION", "datapath commit callback failed"));
  return send_status(fd, d == 1 ? std::string("{}") : done_body);
}

// ------------------------------------------------------------- read path
struct ReadReq {
  uint64_t off;
  uint32_t len;
  uint8_t vtype;
  uint32_t bpc;
  std::vector<uint32_t> crcs;
};

bool handle_read(Server* s, int fd, const Buf& hdr,
                 Buf& scratch) {
  std::string body;
  int ok = run_cb_auth(s, hdr, 0, &body);
  std::vector<ReadReq> reqs;
  uint8_t tag;
  for (;;) {  // collect requests first (client pipelines them + END)
    if (!read_frame(fd, &tag, scratch)) return false;
    if (tag == T_END) break;
    if (tag != T_RCHUNK || scratch.size() < 21) return false;
    ReadReq r;
    memcpy(&r.off, scratch.data(), 8);
    memcpy(&r.len, scratch.data() + 8, 4);
    r.vtype = scratch[12];
    memcpy(&r.bpc, scratch.data() + 13, 4);
    uint32_t n;
    memcpy(&n, scratch.data() + 17, 4);
    if (scratch.size() != 21 + 4 * (size_t)n || n > (1u << 20)) return false;
    r.crcs.resize(n);
    if (n) memcpy(r.crcs.data(), scratch.data() + 21, 4 * (size_t)n);
    reqs.push_back(std::move(r));
  }
  if (ok <= 0)
    return send_status(fd, ok == 0 ? body
                                   : err_json("IO_EXCEPTION",
                                              "datapath auth callback failed"));
  int file_fd = open(body.c_str(), O_RDONLY | O_CLOEXEC);
  if (file_fd < 0)
    return send_status(
        fd, err_json("IO_EXCEPTION", "open " + body + ": " + strerror(errno)));
  // map the block once: in-range chunks are CRC'd out of the page
  // cache and leave via sendfile (zero server-side copies); only
  // EOF-straddling tails fall back to a pooled pread+zero-fill buffer
  struct stat st {};
  size_t fsize = fstat(file_fd, &st) == 0 ? (size_t)st.st_size : 0;
  uint8_t* map = nullptr;
  if (fsize) {
    // MAP_POPULATE wires the PTEs up front: one syscall instead of a
    // minor fault per page while the CRC/writev loop walks the block
    int mflags = MAP_SHARED;
#ifdef MAP_POPULATE
    mflags |= MAP_POPULATE;
#endif
    void* m = mmap(nullptr, fsize, PROT_READ, mflags, file_fd, 0);
    if (m != MAP_FAILED) {
      map = (uint8_t*)m;
#ifdef POSIX_MADV_SEQUENTIAL
      posix_madvise(map, fsize, POSIX_MADV_SEQUENTIAL);
#endif
    }
  }
  // DATA frames accumulate into a pending batch. Chunks that live in
  // the mapping leave via sendfile(2) — the page-cache pages ride into
  // the socket as references, so the server-side copy disappears and
  // the only memcpy left on a GET is the client's recv into its pooled
  // slab. Pooled tail buffers (EOF-straddles) still go out through one
  // gathered writev. The 5-byte frame header before a sendfile payload
  // is sent with MSG_MORE so it lands in the same segment.
  struct PendingSend {
    std::array<uint8_t, 5> hdr;
    const uint8_t* payload;
    uint32_t len;
    PoolBuf* buf;  // null when the payload points into the mapping
  };
  std::vector<PendingSend> pending;
  pending.reserve(reqs.size());
  size_t pending_bytes = 0;
  constexpr size_t kFlushBytes = 8u << 20;
  bool use_sendfile = true;
  auto cleanup = [&](bool ok_close) {
    for (auto& ps : pending)
      if (ps.buf) g_arena.release(ps.buf);
    pending.clear();
    if (map) munmap(map, fsize);
    if (ok_close) close(file_fd);
  };
  auto send_hdr = [&](const std::array<uint8_t, 5>& h) -> bool {
    size_t done = 0;
    while (done < 5) {
      ssize_t w = send(fd, h.data() + done, 5 - done,
                       MSG_MORE | MSG_NOSIGNAL);
      if (w < 0) {
        if (errno == EINTR) continue;
        return false;
      }
      done += (size_t)w;
    }
    return true;
  };
  auto sendfile_full = [&](off_t off, uint32_t len, bool* fell_back)
      -> bool {
    size_t left = len;
    while (left) {
      ssize_t w = sendfile(fd, file_fd, &off, left);
      if (w < 0) {
        if (errno == EINTR) continue;
        if (left == len && (errno == EINVAL || errno == ENOSYS)) {
          // filesystem can't sendfile: nothing sent yet, let the
          // caller writev this payload and stop trying
          *fell_back = true;
          return true;
        }
        return false;
      }
      if (w == 0) return false;
      left -= (size_t)w;
    }
    return true;
  };
  auto flush = [&]() -> bool {
    bool ok = true;
    size_t i = 0;
    auto mapped = [&](const PendingSend& ps) {
      return use_sendfile && !ps.buf && ps.len && ps.payload >= map &&
             ps.payload + ps.len <= map + fsize;
    };
    while (ok && i < pending.size()) {
      if (mapped(pending[i])) {
        bool fell_back = false;
        ok = send_hdr(pending[i].hdr) &&
             sendfile_full((off_t)(pending[i].payload - map),
                           pending[i].len, &fell_back);
        if (ok && fell_back) {
          use_sendfile = false;
          struct iovec iov = {(void*)pending[i].payload, pending[i].len};
          ok = writev_full(fd, &iov, 1);
        }
        i++;
        continue;
      }
      // gather the run of pooled/empty entries into one writev
      std::vector<struct iovec> iov;
      while (i < pending.size() && !mapped(pending[i])) {
        iov.push_back({pending[i].hdr.data(), 5});
        if (pending[i].len)
          iov.push_back({(void*)pending[i].payload, pending[i].len});
        i++;
      }
      ok = writev_full(fd, iov.data(), iov.size());
    }
    for (auto& ps : pending)
      if (ps.buf) g_arena.release(ps.buf);
    pending.clear();
    pending_bytes = 0;
    return ok;
  };
  uint64_t total = 0;
  for (auto& r : reqs) {
    const uint8_t* src = nullptr;
    PoolBuf* pb = nullptr;
    if (map && r.off <= fsize && r.len <= fsize - r.off) {
      src = map + r.off;  // fully in range: serve from the mapping
    } else if (r.len) {
      pb = g_arena.lease(r.len);
      if (!pb) {  // OOM: fail the stream, keep the process
        cleanup(true);
        return send_status(
            fd, err_json("IO_EXCEPTION", "read buffer allocation failed"));
      }
      size_t got = 0;
      while (got < r.len) {
        ssize_t rd = pread(file_fd, pb->p + got, r.len - got,
                           (off_t)(r.off + got));
        if (rd < 0) {
          if (errno == EINTR) continue;
          g_arena.release(pb);
          cleanup(true);
          return send_status(
              fd, err_json("IO_EXCEPTION",
                           "pread: " + std::string(strerror(errno))));
        }
        if (rd == 0) break;  // short: zero-fill tail (store semantics)
        got += (size_t)rd;
      }
      if (got < r.len) memset(pb->p + got, 0, r.len - got);
      src = pb->p;
    }
    if (r.vtype == 1 && !r.crcs.empty()) {
      uint32_t bpc = r.bpc ? r.bpc : r.len;
      size_t slice = 0;
      for (uint32_t o = 0; o < r.len && slice < r.crcs.size();
           o += bpc, slice++) {
        uint32_t n = (r.len - o) < bpc ? (r.len - o) : bpc;
        if (crc32c(src + o, n) != r.crcs[slice]) {
          if (pb) g_arena.release(pb);
          // deliver earlier verified chunks, then the error status
          bool sent = flush();
          s->fail(hdr.data(), (uint32_t)hdr.size());
          char msg[96];
          snprintf(msg, sizeof msg, "checksum mismatch at slice %zu", slice);
          bool st_ok = sent && send_status(fd, err_json("CHECKSUM_MISMATCH",
                                                        msg));
          cleanup(true);
          return st_ok;
        }
      }
    }
    PendingSend ps;
    memcpy(ps.hdr.data(), &r.len, 4);
    ps.hdr[4] = T_DATA;
    ps.payload = src;
    ps.len = r.len;
    ps.buf = pb;
    pending.push_back(ps);
    pending_bytes += r.len;
    total += r.len;
    if (pending_bytes >= kFlushBytes || pending.size() >= 256) {
      if (!flush()) {
        cleanup(true);
        return false;
      }
    }
  }
  if (!flush()) {
    cleanup(true);
    return false;
  }
  cleanup(true);
  std::string done_body;
  int d = run_cb_done(s, hdr, 0, total, (uint32_t)reqs.size(), &done_body);
  if (d < 0)
    return send_status(
        fd, err_json("IO_EXCEPTION", "datapath done callback failed"));
  return send_status(fd, d == 1 ? std::string("{}") : done_body);
}

void conn_loop(Server* s, int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
  // deep buffers: on shared-core rigs every buffer-full forces a
  // client<->server context switch mid-chunk
  int bufsz = 8 * 1024 * 1024;
  setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &bufsz, sizeof bufsz);
  setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &bufsz, sizeof bufsz);
  Buf hdr, scratch;
  for (;;) {
    uint8_t tag;
    if (!read_frame(fd, &tag, hdr)) break;
    bool ok;
    if (tag == T_WHDR)
      ok = handle_write(s, fd, hdr, scratch);
    else if (tag == T_RHDR)
      ok = handle_read(s, fd, hdr, scratch);
    else
      break;
    if (!ok || s->stop.load()) break;
  }
  // erase BEFORE close: dp_stop snapshots s->conns under the lock and
  // shutdown()s each fd — closing first lets the kernel reuse the fd
  // number (a fresh connection or block file) inside that window, and
  // dp_stop would shut down the wrong descriptor
  {
    std::lock_guard<std::mutex> g(s->conn_mu);
    s->conns.erase(fd);
  }
  close(fd);
  s->active--;
}

void accept_loop(Server* s, int listen_fd) {
  for (;;) {
    int fd = accept(listen_fd, nullptr, nullptr);
    if (fd < 0) {
      if (errno == EINTR) continue;
      break;  // listener closed: shutting down
    }
    if (s->stop.load()) {
      close(fd);
      break;
    }
    {
      std::lock_guard<std::mutex> g(s->conn_mu);
      s->conns.insert(fd);
    }
    s->active++;
    std::thread(conn_loop, s, fd).detach();
  }
}

}  // namespace

extern "C" {

void* dp_start(const char* host, int port, dp_auth_cb auth, dp_done_cb done,
               dp_fail_cb fail) {
  int fd = socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (fd < 0) return nullptr;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)port);
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    close(fd);
    return nullptr;
  }
  if (bind(fd, (sockaddr*)&addr, sizeof addr) != 0 || listen(fd, 64) != 0) {
    close(fd);
    return nullptr;
  }
  socklen_t alen = sizeof addr;
  getsockname(fd, (sockaddr*)&addr, &alen);
  Server* s = new Server();
  s->listen_fd = fd;
  s->port = ntohs(addr.sin_port);
  s->auth = auth;
  s->done = done;
  s->fail = fail;
  s->acceptor = std::thread(accept_loop, s, fd);
  // local lane: abstract unix socket (kernel-scoped name, no file to
  // clean up, dies with the process). The random suffix keeps a client
  // that was handed another host's name from ever reaching a
  // coincidentally-matching local sidecar.
  int ufd = socket(AF_UNIX, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (ufd >= 0) {
    uint64_t nonce = 0;
    int rfd = open("/dev/urandom", O_RDONLY | O_CLOEXEC);
    if (rfd >= 0) {
      if (read(rfd, &nonce, sizeof nonce) != sizeof nonce) nonce = 0;
      close(rfd);
    }
    char name[96];
    snprintf(name, sizeof name, "ozone-dp.%d.%d.%016llx", (int)getpid(),
             s->port, (unsigned long long)nonce);
    sockaddr_un ua{};
    ua.sun_family = AF_UNIX;
    size_t nlen = strlen(name);
    memcpy(ua.sun_path + 1, name, nlen);  // sun_path[0]=0: abstract
    socklen_t ulen = (socklen_t)(offsetof(sockaddr_un, sun_path) + 1 + nlen);
    if (bind(ufd, (sockaddr*)&ua, ulen) == 0 && listen(ufd, 64) == 0) {
      s->uds_fd = ufd;
      s->uds_name = std::string("@") + name;
      s->uds_acceptor = std::thread(accept_loop, s, ufd);
    } else {
      close(ufd);
    }
  }
  return s;
}

int dp_port(void* h) { return h ? ((Server*)h)->port : -1; }

// Copies the local-lane abstract socket name ("@..."), returns its
// length; 0 when the unix listener could not be set up.
int dp_uds(void* h, char* out, int cap) {
  if (!h) return 0;
  Server* s = (Server*)h;
  if (s->uds_name.empty() || (int)s->uds_name.size() > cap) return 0;
  memcpy(out, s->uds_name.data(), s->uds_name.size());
  return (int)s->uds_name.size();
}

// Stop accepting, sever live connections, and wait (bounded) for the
// in-flight handlers — their Python callbacks must finish before the
// caller tears down interpreter state.
void dp_stop(void* h) {
  if (!h) return;
  Server* s = (Server*)h;
  s->stop.store(true);
  shutdown(s->listen_fd, SHUT_RDWR);
  close(s->listen_fd);
  if (s->uds_fd >= 0) {
    shutdown(s->uds_fd, SHUT_RDWR);
    close(s->uds_fd);
  }
  {
    std::lock_guard<std::mutex> g(s->conn_mu);
    for (int fd : s->conns) shutdown(fd, SHUT_RDWR);
  }
  if (s->acceptor.joinable()) s->acceptor.join();
  if (s->uds_acceptor.joinable()) s->uds_acceptor.join();
  for (int i = 0; i < 200 && s->active.load() > 0; i++)
    usleep(10 * 1000);
  // leak the Server if a handler is wedged: a use-after-free in a
  // detached thread is worse than 200 bytes at process exit
  if (s->active.load() == 0) delete s;
}

uint32_t dp_crc32c(const void* p, int64_t n) {
  return crc32c((const uint8_t*)p, (size_t)n);
}

// ------------------------------------------------- buffer-pool capsule
// Lease/retain/release handles into the same arena the server's hot
// path uses. Python (ctypes) wraps the returned handle + data pointer
// in a memoryview for zero-copy staging, and releases when done.
void* dp_buf_lease(uint64_t n) { return g_arena.lease((size_t)n); }

void* dp_buf_data(void* b) { return b ? ((PoolBuf*)b)->p : nullptr; }

uint64_t dp_buf_cap(void* b) { return b ? ((PoolBuf*)b)->cap : 0; }

void dp_buf_retain(void* b) {
  if (b) g_arena.retain((PoolBuf*)b);
}

void dp_buf_release(void* b) {
  if (b) g_arena.release((PoolBuf*)b);
}

// which: 0 leased_bytes, 1 free_bytes, 2 high_water_bytes
uint64_t dp_pool_stat(int which) { return g_arena.stat(which); }

}  // extern "C"
