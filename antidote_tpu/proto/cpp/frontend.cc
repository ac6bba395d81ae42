// Native serving front-end (ISSUE 16): accept + framing + decode +
// admission + whole-batch snapshot-cache hits off the GIL.
//
// Extends the epoll io-thread pattern of interdc/cpp/pump.cc (the
// libzmq io-thread role) into the ranch-listener role of the reference
// (antidote_pb_sup.erl:47-56 — 100 acceptors / 1024 conns / {packet,4}
// framing): ONE epoll thread owns the listen socket, every client
// connection's read buffer, 4-byte big-endian length framing, a minimal
// msgpack scan of STATIC_READ_OBJECTS bodies, the admission gate
// (global + per-peer-host in-flight caps, the overload.py semantics),
// and a mirror of the hot-key snapshot cache (entries pushed down from
// Python at writeback time, kept coherent by the one rule stated at
// `struct Frontend`).  A clockless read whose every object resolves
// from the mirror is answered entirely here — byte-identical to the Python
// fast path (proto/server.py _try_cache_read) — and Python only ever
// sees cache misses, writes, interactive txns and foreign-dialect
// frames via one packed batch-drain crossing (frontend_take_batch, one
// GIL acquisition per drain, like pump_take_batch).
//
// Build: python -m antidote_tpu.native_build (pinned flags; embeds the
// source sha for `make native-check`).  No third-party deps.

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <deque>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

constexpr uint32_t MAX_FRAME = 64u * 1024u * 1024u;  // codec.MAX_FRAME
constexpr size_t QUEUE_CAP = 65536;                   // pump.cc discipline
constexpr int MAX_EVENTS = 256;

// message codes (proto/codec.py) + the apb dialect's request codes
// (proto/apb.py APB_REQUEST_CODES) — apb frames always cross to Python
constexpr uint8_t STATIC_READ_OBJECTS = 7;
constexpr uint8_t READ_OBJECTS_RESP = 66;
constexpr uint8_t ERROR_RESP = 127;

bool is_apb(uint8_t c) {
  switch (c) {
    case 116: case 118: case 119: case 120: case 121: case 122:
    case 123: case 129: case 130: case 131:
      return true;
    default:
      return false;
  }
}

// ---------------------------------------------------------------------
// minimal msgpack helpers (canonical shapes — msgpack-python parity)
// ---------------------------------------------------------------------
struct Rd {
  const uint8_t* p;
  const uint8_t* end;
};

inline bool rd_need(const Rd& r, size_t n) {
  return static_cast<size_t>(r.end - r.p) >= n;
}

inline uint16_t be16(const uint8_t* p) {
  return (uint16_t(p[0]) << 8) | p[1];
}
inline uint32_t be32(const uint8_t* p) {
  return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
         (uint32_t(p[2]) << 8) | p[3];
}

// skip one msgpack object; false on malformed/truncated input
bool mp_skip(Rd& r) {
  if (!rd_need(r, 1)) return false;
  uint8_t t = *r.p++;
  size_t n = 0;     // trailing payload bytes
  size_t items = 0; // child objects (array: n, map: 2n)
  if (t <= 0x7f || t >= 0xe0 || t == 0xc0 || t == 0xc2 || t == 0xc3) {
    return true;                       // fixint / nil / bool
  } else if (t >= 0x80 && t <= 0x8f) { // fixmap
    items = size_t(t & 0x0f) * 2;
  } else if (t >= 0x90 && t <= 0x9f) { // fixarray
    items = t & 0x0f;
  } else if (t >= 0xa0 && t <= 0xbf) { // fixstr
    n = t & 0x1f;
  } else {
    switch (t) {
      case 0xc4: case 0xd9:  // bin8 / str8
        if (!rd_need(r, 1)) return false;
        n = *r.p++;
        break;
      case 0xc5: case 0xda:  // bin16 / str16
        if (!rd_need(r, 2)) return false;
        n = be16(r.p); r.p += 2;
        break;
      case 0xc6: case 0xdb:  // bin32 / str32
        if (!rd_need(r, 4)) return false;
        n = be32(r.p); r.p += 4;
        break;
      case 0xcc: case 0xd0: n = 1; break;  // uint8 / int8
      case 0xcd: case 0xd1: n = 2; break;  // uint16 / int16
      case 0xce: case 0xd2: case 0xca: n = 4; break;  // u32/i32/f32
      case 0xcf: case 0xd3: case 0xcb: n = 8; break;  // u64/i64/f64
      case 0xd4: n = 2; break;   // fixext1 (type byte + 1)
      case 0xd5: n = 3; break;
      case 0xd6: n = 5; break;
      case 0xd7: n = 9; break;
      case 0xd8: n = 17; break;
      case 0xc7:  // ext8: len byte + type byte + len payload
        if (!rd_need(r, 2)) return false;
        n = *r.p; r.p += 2;
        break;
      case 0xc8:  // ext16
        if (!rd_need(r, 3)) return false;
        n = be16(r.p); r.p += 3;
        break;
      case 0xc9:  // ext32
        if (!rd_need(r, 5)) return false;
        n = be32(r.p); r.p += 5;
        break;
      case 0xdc:  // array16
        if (!rd_need(r, 2)) return false;
        items = be16(r.p); r.p += 2;
        break;
      case 0xdd:  // array32
        if (!rd_need(r, 4)) return false;
        items = be32(r.p); r.p += 4;
        break;
      case 0xde:  // map16
        if (!rd_need(r, 2)) return false;
        items = size_t(be16(r.p)) * 2; r.p += 2;
        break;
      case 0xdf:  // map32
        if (!rd_need(r, 4)) return false;
        items = size_t(be32(r.p)) * 2; r.p += 4;
        break;
      default:
        return false;  // 0xc1: never used
    }
  }
  if (n) {
    if (!rd_need(r, n)) return false;
    r.p += n;
  }
  for (size_t i = 0; i < items; ++i)
    if (!mp_skip(r)) return false;
  return true;
}

// read a str header; returns payload span or false (non-str)
bool mp_str(Rd& r, const uint8_t** s, size_t* n) {
  if (!rd_need(r, 1)) return false;
  uint8_t t = *r.p;
  if (t >= 0xa0 && t <= 0xbf) {
    *n = t & 0x1f; ++r.p;
  } else if (t == 0xd9) {
    if (!rd_need(r, 2)) return false;
    *n = r.p[1]; r.p += 2;
  } else if (t == 0xda) {
    if (!rd_need(r, 3)) return false;
    *n = be16(r.p + 1); r.p += 3;
  } else if (t == 0xdb) {
    if (!rd_need(r, 5)) return false;
    *n = be32(r.p + 1); r.p += 5;
  } else {
    return false;
  }
  if (!rd_need(r, *n)) return false;
  *s = r.p;
  r.p += *n;
  return true;
}

bool mp_array_hdr(Rd& r, size_t* n) {
  if (!rd_need(r, 1)) return false;
  uint8_t t = *r.p;
  if (t >= 0x90 && t <= 0x9f) {
    *n = t & 0x0f; ++r.p;
  } else if (t == 0xdc) {
    if (!rd_need(r, 3)) return false;
    *n = be16(r.p + 1); r.p += 3;
  } else if (t == 0xdd) {
    if (!rd_need(r, 5)) return false;
    *n = be32(r.p + 1); r.p += 5;
  } else {
    return false;
  }
  return true;
}

bool mp_map_hdr(Rd& r, size_t* n) {
  if (!rd_need(r, 1)) return false;
  uint8_t t = *r.p;
  if (t >= 0x80 && t <= 0x8f) {
    *n = t & 0x0f; ++r.p;
  } else if (t == 0xde) {
    if (!rd_need(r, 3)) return false;
    *n = be16(r.p + 1); r.p += 3;
  } else if (t == 0xdf) {
    if (!rd_need(r, 5)) return false;
    *n = be32(r.p + 1); r.p += 5;
  } else {
    return false;
  }
  return true;
}

// canonical (msgpack-python) packers for the busy reply
void pack_str(std::vector<uint8_t>& o, const char* s, size_t n) {
  if (n < 32) {
    o.push_back(uint8_t(0xa0 | n));
  } else if (n < 256) {
    o.push_back(0xd9);
    o.push_back(uint8_t(n));
  } else {
    o.push_back(0xda);
    o.push_back(uint8_t(n >> 8));
    o.push_back(uint8_t(n));
  }
  o.insert(o.end(), s, s + n);
}
void pack_str(std::vector<uint8_t>& o, const std::string& s) {
  pack_str(o, s.data(), s.size());
}
void pack_uint(std::vector<uint8_t>& o, uint64_t v) {
  if (v < 128) {
    o.push_back(uint8_t(v));
  } else if (v < 256) {
    o.push_back(0xcc);
    o.push_back(uint8_t(v));
  } else if (v < 65536) {
    o.push_back(0xcd);
    o.push_back(uint8_t(v >> 8));
    o.push_back(uint8_t(v));
  } else {
    o.push_back(0xce);
    for (int s = 24; s >= 0; s -= 8) o.push_back(uint8_t(v >> s));
  }
}
void push_be32(std::vector<uint8_t>& o, uint32_t v) {
  for (int s = 24; s >= 0; s -= 8) o.push_back(uint8_t(v >> s));
}

// ---------------------------------------------------------------------
// core structures
// ---------------------------------------------------------------------
// steady_clock is CLOCK_MONOTONIC here, the clock of Python's
// time.monotonic(): stage stamps taken on either side compare directly
long now_us() {
  return long(std::chrono::duration_cast<std::chrono::microseconds>(
                  std::chrono::steady_clock::now().time_since_epoch())
                  .count());
}

struct Frame {
  long conn_id;
  // 0 = conn closed, 1 = admitted frame (aux = the conn's frames already
  // in Python), 2 = shed (aux = hint)
  int kind;
  long aux;
  std::vector<uint8_t> payload;
  long t_arrive_us = 0;  // io thread, frame complete (stage stamp t_arrive)
};

struct Conn {
  int fd = -1;
  long id = 0;
  std::string host;
  std::vector<uint8_t> in;
  std::vector<uint8_t> out;
  size_t out_off = 0;
  long pending = 0;   // queued-to-Python frames awaiting frontend_send
  long admitted = 0;  // of which hold an admission slot
  bool closed = false;
  bool want_out = false;
  bool rd_eof = false;  // peer half-closed; drain replies, then close
  // reply-stage accounting: bytes of `out` already dropped from the
  // buffer, and per frontend_send reply its (end offset in the conn's
  // whole output stream, time handed over) until its last byte is written
  uint64_t out_base = 0;
  std::deque<std::pair<uint64_t, long>> sends;
};

struct ObjSpan {
  const uint8_t* key_b; size_t key_n;
  const uint8_t* type_b; size_t type_n;
  const uint8_t* buck_b; size_t buck_n;
};

struct Entry {
  std::string type_frag;  // packed type-name str fragment
  std::string val;        // packed encode_value(v) fragment
};

struct Frontend {
  int epfd = -1, lfd = -1, wakefd = -1;
  int port = 0;
  std::thread thr;
  std::atomic<bool> stop{false};

  std::mutex mu;
  std::condition_variable cv;
  std::deque<Frame> q;

  std::unordered_map<long, Conn> conns;
  std::unordered_map<int, long> by_fd;
  std::vector<long> out_dirty;  // conns with output buffered off-thread
  long next_id = 1;
  long n_open = 0;
  bool accept_paused = false;

  int max_conns = 1024;
  long max_in_flight = 256;
  long max_per_host = 64;
  long g_inflight = 0;
  long shed_streak = 0;
  std::unordered_map<std::string, long> host_inflight;

  // The mirror, and the ONE rule that keeps it coherent under writes.
  //
  // Invariant: the mirror never holds a value that was read before an
  // invalidation of its key.  So every entry is the key's value in the
  // epoch being served and in every later one until its key is next
  // invalidated, and the io thread may answer from any entry it finds.
  //
  // What keeps it, all of it in three functions below:
  //  * invalidate_locked erases the key's entry AND marks the key in
  //    `dead`, entry or no entry (a bottom that Python is about to teach
  //    has none);
  //  * fill_locked, which every fill passes through, takes an entry only
  //    if it is stamped with `cur_epoch` and its key is not marked.  A
  //    value gathered (or a bottom, or a re-proved cache entry, read) at
  //    epoch E and pushed after a commit invalidated its key is refused:
  //    it arrives either while E is still served, and finds the mark, or
  //    after the advance, with another stamp than the one served;
  //  * frontend_advance moves `cur_epoch` and drops the marks: no fill
  //    stamped with an earlier epoch is taken any more, and a value read
  //    at the new epoch was read after everything applied before it.
  // It rests on one thing the callers hold to (store/kv.py, txn/
  // manager.py): invalidations and advances happen under the commit lock,
  // the advance to E in the critical section that publishes E — so an
  // invalidation always finds the mirror at the epoch Python serves.
  // A read the mirror cannot answer is a miss: it crosses to Python.
  std::unordered_map<std::string, Entry> mirror;
  size_t mirror_cap = 1u << 18;
  // keys invalidated while cur_epoch was served (bounded: dropped at every
  // advance; past mirror_cap of them `dead_all` refuses every fill instead)
  std::unordered_set<std::string> dead;
  bool dead_all = false;
  long cur_epoch = -1;
  bool clockless_ok = false;
  bool fast_serve = true;
  std::string clock_frag;  // packed commit_clock int-list fragment

  // stats (all under mu except where noted)
  long st_accept = 0, st_closed = 0, st_frames = 0, st_hits = 0,
       st_hit_objs = 0, st_shed = 0, st_fwd = 0, st_bad_frame = 0;
  // stage accounting (ISSUE 24): arrival -> taken by Python, summed over
  // admitted frames, and frontend_send -> last byte written
  long st_cross_wait_us = 0, st_cross_frames = 0, st_send_wait_us = 0,
       st_send_frames = 0;
  // calls of frontend_send / frontend_send_many (one per reply batch)
  long st_send_calls = 0;
  // calls of frontend_fill / frontend_fill_many, and the entries they
  // carried (a writeback launch's gathered keys are one call)
  long st_fill_calls = 0, st_fill_keys = 0;
  // of those entries, the ones the rule above turned away
  long st_fill_refused = 0;
  // calls of frontend_invalidate_many, and the keys they named
  long st_inval_calls = 0, st_inval_keys = 0;
  std::atomic<long> st_drains{0};

  std::vector<ObjSpan> scratch_objs;
};

void wake(Frontend* f) {
  uint64_t one = 1;
  ssize_t r = write(f->wakefd, &one, sizeof(one));
  (void)r;
}

void arm_out(Frontend* f, Conn& c) {
  if (c.fd < 0 || c.want_out) return;
  epoll_event ev{};
  // after a half-close the fd stays level-triggered-readable forever —
  // poll only the write side once rd_eof is set
  ev.events = (c.rd_eof ? 0 : EPOLLIN) | EPOLLOUT;
  ev.data.fd = c.fd;
  epoll_ctl(f->epfd, EPOLL_CTL_MOD, c.fd, &ev);
  c.want_out = true;
}

void disarm_out(Frontend* f, Conn& c) {
  if (c.fd < 0 || !c.want_out) return;
  epoll_event ev{};
  ev.events = c.rd_eof ? 0 : EPOLLIN;
  ev.data.fd = c.fd;
  epoll_ctl(f->epfd, EPOLL_CTL_MOD, c.fd, &ev);
  c.want_out = false;
}

// flush as much buffered output as the socket accepts (mu held)
void flush_out(Frontend* f, Conn& c) {
  bool again = false;
  while (c.fd >= 0 && c.out_off < c.out.size()) {
    ssize_t w = send(c.fd, c.out.data() + c.out_off,
                     c.out.size() - c.out_off, MSG_DONTWAIT | MSG_NOSIGNAL);
    if (w > 0) {
      c.out_off += size_t(w);
    } else if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      again = true;
      break;
    } else {
      break;  // peer gone; the read side will close the conn
    }
  }
  // replies Python handed over whose last byte is now written
  uint64_t sent = c.out_base + c.out_off;
  if (!c.sends.empty() && c.sends.front().first <= sent) {
    long now = now_us();
    while (!c.sends.empty() && c.sends.front().first <= sent) {
      f->st_send_wait_us += now - c.sends.front().second;
      ++f->st_send_frames;
      c.sends.pop_front();
    }
  }
  if (again) {
    arm_out(f, c);
    return;
  }
  if (c.out_off >= c.out.size()) {
    c.out_base += c.out.size();
    c.out.clear();
    c.out_off = 0;
    disarm_out(f, c);
  }
}

void resume_accept(Frontend* f) {
  if (!f->accept_paused) return;
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.fd = f->lfd;
  epoll_ctl(f->epfd, EPOLL_CTL_ADD, f->lfd, &ev);
  f->accept_paused = false;
}

// close the socket; keep a tombstone while Python still owes replies so
// the admission decrements in frontend_send find their host (mu held)
void close_conn(Frontend* f, long cid) {
  auto it = f->conns.find(cid);
  if (it == f->conns.end()) return;
  Conn& c = it->second;
  if (c.fd >= 0) {
    epoll_ctl(f->epfd, EPOLL_CTL_DEL, c.fd, nullptr);
    f->by_fd.erase(c.fd);
    ::close(c.fd);
    c.fd = -1;
    --f->n_open;
    ++f->st_closed;
    resume_accept(f);
  }
  if (c.closed) return;
  c.closed = true;
  c.in.clear();
  c.in.shrink_to_fit();
  c.out.clear();
  c.sends.clear();
  // conn-drop sentinel: the bridge tears down the conn worker and
  // aborts orphaned interactive txns (Handler.handle's finally)
  f->q.push_back(Frame{cid, 0, 0, {}, 0});
  f->cv.notify_all();
  if (c.pending <= 0) f->conns.erase(it);
}

// half-close parity with the Python plane: a client that shut down its
// write side still receives every reply it is owed — the conn closes
// only once no crossed frame is pending AND the out buffer drained
// (mu held)
void maybe_close_eof(Frontend* f, long cid) {
  auto it = f->conns.find(cid);
  if (it == f->conns.end()) return;
  Conn& c = it->second;
  if (!c.rd_eof || c.closed) return;
  if (c.pending > 0 || c.out_off < c.out.size()) return;
  close_conn(f, cid);
}

// enqueue with the pump.cc backpressure discipline: a full crossing
// queue pauses the io thread (TCP backpressure), never grows unbounded.
// Returns with mu held; lk must hold mu on entry.
void enqueue(Frontend* f, std::unique_lock<std::mutex>& lk, Frame&& fr) {
  while (f->q.size() >= QUEUE_CAP && !f->stop.load()) {
    lk.unlock();
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
    lk.lock();
  }
  f->q.push_back(std::move(fr));
  f->cv.notify_all();
}

// parse a STATIC_READ_OBJECTS body (payload after the code byte) into
// per-object key/type/bucket spans.  Returns false when the read is not
// natively servable (clocked, deadline-bearing, malformed, non-map) —
// the frame is forwarded and Python owns parity.
bool parse_read(const uint8_t* body, size_t len,
                std::vector<ObjSpan>& objs) {
  objs.clear();
  Rd r{body, body + len};
  size_t pairs;
  if (!mp_map_hdr(r, &pairs)) return false;
  bool saw_objects = false;
  for (size_t i = 0; i < pairs; ++i) {
    const uint8_t* ks; size_t kn;
    if (!mp_str(r, &ks, &kn)) return false;
    if (kn == 7 && memcmp(ks, "objects", 7) == 0) {
      size_t n;
      if (!mp_array_hdr(r, &n)) return false;
      if (n > (1u << 20)) return false;
      objs.reserve(n);
      for (size_t j = 0; j < n; ++j) {
        size_t m;
        if (!mp_array_hdr(r, &m) || m != 3) return false;
        ObjSpan o{};
        o.key_b = r.p;
        if (!mp_skip(r)) return false;
        o.key_n = size_t(r.p - o.key_b);
        o.type_b = r.p;
        if (!mp_skip(r)) return false;
        o.type_n = size_t(r.p - o.type_b);
        o.buck_b = r.p;
        if (!mp_skip(r)) return false;
        o.buck_n = size_t(r.p - o.buck_b);
        objs.push_back(o);
      }
      saw_objects = true;
    } else if (kn == 5 && memcmp(ks, "clock", 5) == 0) {
      // clockless only: a session clock routes through Python (the
      // epoch-comparison discipline lives in _try_cache_read)
      if (!rd_need(r, 1) || *r.p != 0xc0) return false;
      ++r.p;
    } else if (kn == 11 && memcmp(ks, "deadline_ms", 11) == 0) {
      return false;  // deadline semantics stay with Python
    } else {
      if (!mp_skip(r)) return false;  // ignore unknown keys, like Python
    }
  }
  return saw_objects && r.p == r.end;
}

// hand-build the byte-identical Python fast-path reply:
// encode(READ_OBJECTS_RESP, {"values": [...], "commit_clock": [...]})
void build_hit_reply(Frontend* f, Conn& c,
                     const std::vector<const Entry*>& hits) {
  size_t n = hits.size();
  size_t arr_hdr = n < 16 ? 1 : (n < 65536 ? 3 : 5);
  size_t body = 1 + 7 + arr_hdr + 13 + f->clock_frag.size();
  for (const Entry* e : hits) body += e->val.size();
  std::vector<uint8_t>& o = c.out;
  o.reserve(o.size() + 5 + body);
  push_be32(o, uint32_t(body + 1));
  o.push_back(READ_OBJECTS_RESP);
  o.push_back(0x82);  // fixmap(2)
  pack_str(o, "values", 6);
  if (n < 16) {
    o.push_back(uint8_t(0x90 | n));
  } else if (n < 65536) {
    o.push_back(0xdc);
    o.push_back(uint8_t(n >> 8));
    o.push_back(uint8_t(n));
  } else {
    o.push_back(0xdd);
    push_be32(o, uint32_t(n));
  }
  for (const Entry* e : hits)
    o.insert(o.end(), e->val.begin(), e->val.end());
  pack_str(o, "commit_clock", 12);
  o.insert(o.end(), f->clock_frag.begin(), f->clock_frag.end());
}

// typed busy reply in the native dialect (overload.py semantics):
// encode(ERROR_RESP, {"error": "busy", "detail": ..., "retry_after_ms": N})
void build_busy_reply(Conn& c, const std::string& detail, long hint) {
  std::vector<uint8_t> body;
  body.reserve(64 + detail.size());
  body.push_back(0x83);
  pack_str(body, "error", 5);
  pack_str(body, "busy", 4);
  pack_str(body, "detail", 6);
  pack_str(body, detail);
  pack_str(body, "retry_after_ms", 14);
  pack_uint(body, uint64_t(hint));
  std::vector<uint8_t>& o = c.out;
  push_be32(o, uint32_t(body.size() + 1));
  o.push_back(ERROR_RESP);
  o.insert(o.end(), body.begin(), body.end());
}

long retry_hint(Frontend* f) {
  // overload.retry_hint_ms: pressure-scaled, bounded 25..500 ms
  ++f->shed_streak;
  long h = 25 * (1 + f->shed_streak / 4);
  return h < 25 ? 25 : (h > 500 ? 500 : h);
}

// one complete frame from conn `c` (mu held via lk)
void on_frame(Frontend* f, std::unique_lock<std::mutex>& lk, long cid,
              const uint8_t* payload, size_t len) {
  auto it = f->conns.find(cid);
  if (it == f->conns.end()) return;
  Conn* c = &it->second;
  ++f->st_frames;
  uint8_t code = len ? payload[0] : 0;
  bool apb = len && is_apb(code);

  // ---- native whole-batch cache hit (the headline path) -------------
  if (!apb && code == STATIC_READ_OBJECTS && f->fast_serve &&
      f->clockless_ok && f->cur_epoch >= 0 && c->pending == 0 &&
      parse_read(payload + 1, len - 1, f->scratch_objs) &&
      !f->scratch_objs.empty()) {
    std::vector<const Entry*> hits;
    hits.reserve(f->scratch_objs.size());
    std::string k;
    bool all = true;
    for (const ObjSpan& o : f->scratch_objs) {
      k.assign(reinterpret_cast<const char*>(o.key_b), o.key_n);
      k.append(reinterpret_cast<const char*>(o.buck_b), o.buck_n);
      auto e = f->mirror.find(k);
      if (e == f->mirror.end() ||
          e->second.type_frag.size() != o.type_n ||
          memcmp(e->second.type_frag.data(), o.type_b, o.type_n) != 0) {
        all = false;
        break;
      }
      hits.push_back(&e->second);
    }
    if (all) {
      ++f->st_hits;
      f->st_hit_objs += long(hits.size());
      build_hit_reply(f, *c, hits);
      flush_out(f, *c);
      return;
    }
  }

  // ---- admission (overload.py AdmissionGate, natively) --------------
  std::string detail;
  if (f->g_inflight >= f->max_in_flight) {
    detail = "server at max_in_flight=" + std::to_string(f->max_in_flight);
  } else {
    long ph = 0;
    auto hi = f->host_inflight.find(c->host);
    if (hi != f->host_inflight.end()) ph = hi->second;
    if (ph >= f->max_per_host)
      detail = "client " + c->host + " at max_in_flight_per_client=" +
               std::to_string(f->max_per_host);
  }
  if (!detail.empty()) {
    ++f->st_shed;
    long hint = retry_hint(f);
    if (apb || c->pending > 0) {
      // apb busy replies are built by the apb codec, and a conn with
      // in-flight Python replies must keep per-conn reply order — both
      // cross as a shed frame the bridge answers in the frame's dialect
      c->pending += 1;
      Frame fr{cid, 2, hint, {}, now_us()};
      fr.payload.assign(payload, payload + len);
      enqueue(f, lk, std::move(fr));
    } else {
      build_busy_reply(*c, detail, hint);
      flush_out(f, *c);
    }
    return;
  }

  // ---- admitted: cross to Python in the next drain ------------------
  f->shed_streak = 0;
  ++f->g_inflight;
  ++f->host_inflight[c->host];
  c->pending += 1;
  c->admitted += 1;
  ++f->st_fwd;
  // aux: this conn's frames Python still owes a reply, not counting this
  // one — 0 tells the bridge nothing of the conn is in Python, so the
  // reply to this frame may leave from whichever stage answers it
  Frame fr{cid, 1, c->pending - 1, {}, now_us()};
  fr.payload.assign(payload, payload + len);
  enqueue(f, lk, std::move(fr));
}

// drain every complete frame out of a conn's read buffer (mu held)
void drain_in(Frontend* f, std::unique_lock<std::mutex>& lk, long cid) {
  size_t off = 0;
  for (;;) {
    auto it = f->conns.find(cid);
    if (it == f->conns.end() || it->second.closed) return;
    Conn& c = it->second;
    if (c.in.size() - off < 4) break;
    uint32_t n = be32(c.in.data() + off);
    if (n < 1 || n > MAX_FRAME) {
      // codec read_frame_buffered raises ConnectionError here — the
      // Python server drops the conn silently; mirror that
      ++f->st_bad_frame;
      close_conn(f, cid);
      return;
    }
    if (c.in.size() - off < 4 + size_t(n)) break;
    // on_frame may release mu during enqueue backpressure; keep the
    // bytes alive independently of the (re-lookupable) conn buffer
    off += 4;
    std::vector<uint8_t> payload(c.in.begin() + off,
                                 c.in.begin() + off + n);
    off += n;
    on_frame(f, lk, cid, payload.data(), payload.size());
  }
  auto it = f->conns.find(cid);
  if (it == f->conns.end()) return;
  Conn& c = it->second;
  if (off) c.in.erase(c.in.begin(), c.in.begin() + off);
}

void do_accept(Frontend* f, std::unique_lock<std::mutex>& lk) {
  for (;;) {
    sockaddr_in sa{};
    socklen_t sl = sizeof(sa);
    int fd = accept4(f->lfd, reinterpret_cast<sockaddr*>(&sa), &sl,
                     SOCK_NONBLOCK | SOCK_CLOEXEC);
    if (fd < 0) return;
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    char hbuf[INET_ADDRSTRLEN] = "?";
    inet_ntop(AF_INET, &sa.sin_addr, hbuf, sizeof(hbuf));
    long cid = f->next_id++;
    Conn c;
    c.fd = fd;
    c.id = cid;
    c.host = hbuf;
    f->by_fd[fd] = cid;
    f->conns.emplace(cid, std::move(c));
    ++f->n_open;
    ++f->st_accept;
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.fd = fd;
    epoll_ctl(f->epfd, EPOLL_CTL_ADD, fd, &ev);
    if (f->n_open >= f->max_conns) {
      // ranch-style backpressure: park accepting, let the kernel
      // listen backlog hold the excess (listen() backlog == cap)
      epoll_ctl(f->epfd, EPOLL_CTL_DEL, f->lfd, nullptr);
      f->accept_paused = true;
      return;
    }
  }
}

void io_loop(Frontend* f) {
  epoll_event evs[MAX_EVENTS];
  std::vector<uint8_t> buf(1 << 16);
  while (!f->stop.load()) {
    int n = epoll_wait(f->epfd, evs, MAX_EVENTS, 100);
    if (f->stop.load()) return;
    std::unique_lock<std::mutex> lk(f->mu);
    // output buffered by frontend_send while we slept
    if (!f->out_dirty.empty()) {
      for (long cid : f->out_dirty) {
        auto it = f->conns.find(cid);
        if (it != f->conns.end() && !it->second.closed)
          flush_out(f, it->second);
        maybe_close_eof(f, cid);
      }
      f->out_dirty.clear();
    }
    for (int i = 0; i < n; ++i) {
      int fd = evs[i].data.fd;
      if (fd == f->wakefd) {
        uint64_t junk;
        ssize_t r = read(f->wakefd, &junk, sizeof(junk));
        (void)r;
        continue;
      }
      if (fd == f->lfd) {
        do_accept(f, lk);
        continue;
      }
      auto bi = f->by_fd.find(fd);
      if (bi == f->by_fd.end()) continue;
      long cid = bi->second;
      if (evs[i].events & (EPOLLHUP | EPOLLERR)) {
        close_conn(f, cid);
        continue;
      }
      if (evs[i].events & EPOLLOUT) {
        auto it = f->conns.find(cid);
        if (it != f->conns.end()) flush_out(f, it->second);
        maybe_close_eof(f, cid);
      }
      if (evs[i].events & EPOLLIN) {
        bool eof = false, err = false;
        for (;;) {
          ssize_t r = recv(fd, buf.data(), buf.size(), MSG_DONTWAIT);
          if (r > 0) {
            auto it = f->conns.find(cid);
            if (it == f->conns.end()) break;
            it->second.in.insert(it->second.in.end(), buf.data(),
                                 buf.data() + r);
            if (size_t(r) < buf.size()) break;
          } else if (r == 0) {
            eof = true;
            break;
          } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
            break;
          } else {
            err = true;
            break;
          }
        }
        drain_in(f, lk, cid);
        if (err) {
          close_conn(f, cid);
        } else if (eof) {
          auto it = f->conns.find(cid);
          if (it != f->conns.end() && !it->second.closed) {
            Conn& c = it->second;
            c.rd_eof = true;
            epoll_event ev{};
            ev.events = c.want_out ? EPOLLOUT : 0;
            ev.data.fd = c.fd;
            epoll_ctl(f->epfd, EPOLL_CTL_MOD, c.fd, &ev);
            maybe_close_eof(f, cid);
          }
        }
      }
    }
  }
}

// append one fully-framed reply for `conn_id` (len may be 0: account
// only), release `n_admitted` admission slots, keep per-conn order.
// True when the io thread has something to do for it (mu held).
bool send_locked(Frontend* f, long conn_id, const uint8_t* buf, long len,
                 long n_admitted) {
  auto it = f->conns.find(conn_id);
  if (n_admitted > 0) {
    f->g_inflight -= n_admitted;
    if (f->g_inflight < 0) f->g_inflight = 0;
    if (it != f->conns.end()) {
      auto hi = f->host_inflight.find(it->second.host);
      if (hi != f->host_inflight.end()) {
        hi->second -= n_admitted;
        if (hi->second <= 0) f->host_inflight.erase(hi);
      }
    }
  }
  if (it == f->conns.end()) return false;
  Conn& c = it->second;
  bool work = false;
  c.pending -= 1;
  c.admitted -= n_admitted;
  if (!c.closed && len > 0) {
    bool was_empty = c.out.empty();
    c.out.insert(c.out.end(), buf, buf + len);
    c.sends.emplace_back(c.out_base + c.out.size(), now_us());
    if (was_empty) f->out_dirty.push_back(conn_id);
    work = true;
  } else if (!c.closed && c.rd_eof && c.pending <= 0) {
    // half-closed conn just got its last (empty) reply: have the io
    // thread run the deferred close
    f->out_dirty.push_back(conn_id);
    work = true;
  }
  if (c.closed && c.pending <= 0) f->conns.erase(it);
  return work;
}

// one mirror entry, caller holds f->mu.  Every fill passes through here,
// and here is the rule (see `struct Frontend`): only a value read at the
// epoch being served, of a key not invalidated in it, is taken.
void fill_locked(Frontend* f, const uint8_t* key, long key_len,
                 const uint8_t* type_frag, long type_len,
                 const uint8_t* val, long val_len, long epoch_id) {
  ++f->st_fill_keys;
  std::string k(reinterpret_cast<const char*>(key), size_t(key_len));
  if (epoch_id != f->cur_epoch || f->dead_all || f->dead.count(k)) {
    ++f->st_fill_refused;
    return;
  }
  if (f->mirror.size() >= f->mirror_cap && !f->mirror.count(k)) {
    f->mirror.erase(f->mirror.begin());  // capacity cap, arbitrary victim
  }
  Entry& e = f->mirror[k];
  e.type_frag.assign(reinterpret_cast<const char*>(type_frag),
                     size_t(type_len));
  e.val.assign(reinterpret_cast<const char*>(val), size_t(val_len));
}

// one invalidated key, caller holds f->mu: the entry goes and the key is
// marked, so that no value read before this call comes (back) in
void invalidate_locked(Frontend* f, const uint8_t* key, long key_len) {
  std::string k(reinterpret_cast<const char*>(key), size_t(key_len));
  f->mirror.erase(k);
  if (f->dead_all) return;
  if (f->dead.size() >= f->mirror_cap) {
    f->dead.clear();
    f->dead_all = true;
  } else {
    f->dead.insert(std::move(k));
  }
}

}  // namespace

#ifndef ANTIDOTE_SRC_SHA
#define ANTIDOTE_SRC_SHA "unknown"
#endif

extern "C" {

const char* frontend_src_sha() { return ANTIDOTE_SRC_SHA; }

void* frontend_create(const char* host, int port, int max_conns,
                      long max_in_flight, long max_per_host,
                      long mirror_cap) {
  Frontend* f = new Frontend();
  f->max_conns = max_conns > 0 ? max_conns : 1024;
  f->max_in_flight = max_in_flight > 0 ? max_in_flight : 256;
  f->max_per_host = max_per_host > 0 ? max_per_host : 64;
  if (mirror_cap > 0) f->mirror_cap = size_t(mirror_cap);
  f->lfd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK | SOCK_CLOEXEC, 0);
  if (f->lfd < 0) {
    delete f;
    return nullptr;
  }
  int one = 1;
  setsockopt(f->lfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_port = htons(uint16_t(port));
  if (inet_pton(AF_INET, host, &sa.sin_addr) != 1)
    sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  if (bind(f->lfd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
      listen(f->lfd, f->max_conns) != 0) {
    ::close(f->lfd);
    delete f;
    return nullptr;
  }
  socklen_t sl = sizeof(sa);
  getsockname(f->lfd, reinterpret_cast<sockaddr*>(&sa), &sl);
  f->port = ntohs(sa.sin_port);
  f->epfd = epoll_create1(EPOLL_CLOEXEC);
  f->wakefd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  if (f->epfd < 0 || f->wakefd < 0) {
    ::close(f->lfd);
    if (f->epfd >= 0) ::close(f->epfd);
    if (f->wakefd >= 0) ::close(f->wakefd);
    delete f;
    return nullptr;
  }
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.fd = f->lfd;
  epoll_ctl(f->epfd, EPOLL_CTL_ADD, f->lfd, &ev);
  ev.data.fd = f->wakefd;
  epoll_ctl(f->epfd, EPOLL_CTL_ADD, f->wakefd, &ev);
  f->thr = std::thread(io_loop, f);
  return f;
}

int frontend_port(void* h) {
  return static_cast<Frontend*>(h)->port;
}

// pack the drained crossing like pump_take_batch: payloads back-to-back
// in `out`, 5 longs per frame in `descs` (conn_id, kind, len, aux,
// t_arrive_us: the io thread's monotonic stamp of the complete frame).
// Returns n frames, 0 on timeout, -1 when stopped, -2 when the first
// frame alone exceeds `cap` (descs[0..3] then carry its needs).
long frontend_take_batch(void* h, uint8_t* out, long cap, long* descs,
                         long max_n, long timeout_ms) {
  Frontend* f = static_cast<Frontend*>(h);
  std::unique_lock<std::mutex> lk(f->mu);
  if (f->q.empty()) {
    f->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                   [&] { return !f->q.empty() || f->stop.load(); });
  }
  if (f->q.empty()) return f->stop.load() ? -1 : 0;
  long n = 0, used = 0;
  long now = now_us();
  while (n < max_n && !f->q.empty()) {
    Frame& fr = f->q.front();
    long need = long(fr.payload.size());
    if (used + need > cap) {
      if (n == 0) {
        descs[0] = fr.conn_id;
        descs[1] = fr.kind;
        descs[2] = need;
        descs[3] = fr.aux;
        return -2;
      }
      break;
    }
    memcpy(out + used, fr.payload.data(), size_t(need));
    descs[n * 5 + 0] = fr.conn_id;
    descs[n * 5 + 1] = fr.kind;
    descs[n * 5 + 2] = need;
    descs[n * 5 + 3] = fr.aux;
    descs[n * 5 + 4] = fr.t_arrive_us;
    if (fr.kind == 1) {
      f->st_cross_wait_us += now - fr.t_arrive_us;
      ++f->st_cross_frames;
    }
    used += need;
    ++n;
    f->q.pop_front();
  }
  f->st_drains.fetch_add(1);
  return n;
}

void frontend_send(void* h, long conn_id, const uint8_t* buf, long len,
                   long n_admitted) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  ++f->st_send_calls;
  if (send_locked(f, conn_id, buf, len, n_admitted)) wake(f);
}

// a batch of replies in one crossing: `n` frames back-to-back in `buf`,
// 3 longs per frame in `descs` (conn_id, len, n_admitted).  Per frame the
// accounting of frontend_send; one mu take and one wake for all of them.
void frontend_send_many(void* h, long n, const long* descs,
                        const uint8_t* buf) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  ++f->st_send_calls;
  bool work = false;
  for (long i = 0; i < n; ++i) {
    long len = descs[i * 3 + 1];
    work |= send_locked(f, descs[i * 3], buf, len, descs[i * 3 + 2]);
    buf += len;
  }
  if (work) wake(f);
}

void frontend_close_conn(void* h, long conn_id) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  close_conn(f, conn_id);
}

// mirror protocol ------------------------------------------------------
// advance to serving epoch `epoch_id`, called in the critical section
// that publishes it: every entry survives (every mutation since it was
// taken invalidated its key eagerly under the commit lock), the marks of
// the epoch left behind drop (fills stamped with it are refused by their
// stamp from here on).
void frontend_advance(void* h, long epoch_id, const uint8_t* clock_frag,
                      long clock_len, int clockless_ok) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  if (epoch_id != f->cur_epoch) {
    f->cur_epoch = epoch_id;
    f->dead.clear();
    f->dead_all = false;
  }
  f->clock_frag.assign(reinterpret_cast<const char*>(clock_frag),
                       size_t(clock_len));
  f->clockless_ok = clockless_ok != 0;
}

void frontend_fill(void* h, const uint8_t* key, long key_len,
                   const uint8_t* type_frag, long type_len,
                   const uint8_t* val, long val_len, long epoch_id) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  ++f->st_fill_calls;
  fill_locked(f, key, key_len, type_frag, type_len, val, val_len,
              epoch_id);
}

// a batch of fills in one crossing: `n` entries back-to-back in `buf`
// (key, type fragment, value), 3 longs per entry in `descs` (their
// lengths), all stamped `epoch_id`.  Per entry what frontend_fill does;
// one mu take for all of them.
void frontend_fill_many(void* h, long n, const long* descs,
                        const uint8_t* buf, long epoch_id) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  ++f->st_fill_calls;
  for (long i = 0; i < n; ++i) {
    long kl = descs[i * 3], tl = descs[i * 3 + 1], vl = descs[i * 3 + 2];
    fill_locked(f, buf, kl, buf + kl, tl, buf + kl + tl, vl, epoch_id);
    buf += kl + tl + vl;
  }
}

// invalidate `n` keys in one crossing (a commit group's written keys):
// the keys back-to-back in `buf`, their lengths in `lens`; one mu take
// for all of them, so no hit is built between two keys of one group.
void frontend_invalidate_many(void* h, long n, const long* lens,
                              const uint8_t* buf) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  ++f->st_inval_calls;
  f->st_inval_keys += n;
  for (long i = 0; i < n; ++i) {
    invalidate_locked(f, buf, lens[i]);
    buf += lens[i];
  }
}

// whole-batch hits answered so far: reads the epoch plane served that
// Python never saw (the txn manager's idle test counts them as reads)
long frontend_native_hits(void* h) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  return f->st_hits;
}

void frontend_mirror_reset(void* h) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  f->mirror.clear();
  f->dead.clear();
  f->dead_all = false;
  f->cur_epoch = -1;
  f->clockless_ok = false;
}

void frontend_set_fast_serve(void* h, int on) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  f->fast_serve = on != 0;
}

void frontend_set_clockless_ok(void* h, int on) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  f->clockless_ok = on != 0;
}

// stats snapshot: [accepted, closed, frames, native_hits, hit_objects,
//                  sheds, forwarded, drains, mirror_size, in_flight,
//                  open_conns, bad_frames, cross_wait_us, cross_frames,
//                  send_wait_us, send_frames, send_calls, fill_calls,
//                  fill_keys, fill_refused, invalidate_calls,
//                  invalidate_keys, mirror_marks]
void frontend_stats(void* h, long* out, int n) {
  Frontend* f = static_cast<Frontend*>(h);
  std::lock_guard<std::mutex> lk(f->mu);
  long vals[] = {f->st_accept, f->st_closed, f->st_frames, f->st_hits,
                   f->st_hit_objs, f->st_shed, f->st_fwd,
                   f->st_drains.load(), long(f->mirror.size()),
                   f->g_inflight, f->n_open, f->st_bad_frame,
                   f->st_cross_wait_us, f->st_cross_frames,
                   f->st_send_wait_us, f->st_send_frames,
                   f->st_send_calls, f->st_fill_calls, f->st_fill_keys,
                   f->st_fill_refused, f->st_inval_calls, f->st_inval_keys,
                   long(f->dead_all ? f->mirror_cap : f->dead.size())};
  const int have = int(sizeof(vals) / sizeof(vals[0]));
  for (int i = 0; i < n && i < have; ++i) out[i] = vals[i];
}

void frontend_stop(void* h) {
  Frontend* f = static_cast<Frontend*>(h);
  f->stop.store(true);
  wake(f);
  {
    std::lock_guard<std::mutex> lk(f->mu);
    f->cv.notify_all();
  }
  if (f->thr.joinable()) f->thr.join();
  std::lock_guard<std::mutex> lk(f->mu);
  for (auto& kv : f->conns) {
    if (kv.second.fd >= 0) {
      // replies handed over since the io thread's last round (the typed
      // errors of a shutdown): one non-blocking write each, then close
      Conn& c = kv.second;
      if (c.out_off < c.out.size()) {
        ssize_t w = send(c.fd, c.out.data() + c.out_off,
                         c.out.size() - c.out_off,
                         MSG_DONTWAIT | MSG_NOSIGNAL);
        (void)w;
      }
      ::close(c.fd);
      c.fd = -1;
    }
  }
  f->conns.clear();
  f->by_fd.clear();
  if (f->lfd >= 0) ::close(f->lfd);
  if (f->epfd >= 0) ::close(f->epfd);
  if (f->wakefd >= 0) ::close(f->wakefd);
  f->lfd = f->epfd = f->wakefd = -1;
}

// never deleted: a racing frontend_take_batch may still sit in the cv
// wait — the quarantined struct outlives it (the pump_free discipline)
void frontend_free(void* h) {
  Frontend* f = static_cast<Frontend*>(h);
  if (!f->stop.load()) frontend_stop(h);
}

}  // extern "C"
